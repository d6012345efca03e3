"""What the program itself writes into a profiler trace, for the readers of
its per-layer metrics: its own host spans (``api.*``, from
``repro.obs.device_span``) and the HLO of each program it ran.

A stop-gap: ``xplane.read`` keeps the benchmark's spans and each op's HLO
name only, and this module wraps it rather than edit it.  ``install()``,
called by a reader when it is loaded, has every ``TraceView`` that
``xplane.read`` returns also carry:

- ``program_spans``: ``[(name, start_ns, end_ns, attrs)]`` of the host
  events named ``api.*``, on the trace's clock;
- ``programs``: ``{program: Program}`` of every program the process had
  loaded.  The profiler keeps each program's optimized HLO in the
  ``/host:metadata`` plane, one event per program, named
  ``<module>(<id>)``.  Its instruction names are the op names of the
  device trace, and each instruction's ``op_name`` holds the
  ``jax.named_scope`` path it was traced under.  On a TPU an execution's
  XLA Modules event carries the same name; on the CPU it carries the
  module alone, and its ops carry the id;
- ``programs_run``: those ``<module>(<id>)`` names of the CPU's ops in
  the window.

The file is read twice more for it (``ProfileData`` for the spans, the
raw protobuf for the HLO), and a reader that runs before ``install()``
would find nothing.  Both go once ``xplane.read`` collects these fields
itself (ROADMAP, Queue 1 item 9).  The HLO is decoded here, field by
field, so that nothing beyond JAX is imported into the process that
holds the chip.  Where the program writes no such marks (an older
program), ``program_spans`` is empty and no op carries a scope, and the
readers of spans and scopes find nothing to read; a program's output
count is in its HLO whatever it marks.
"""

from __future__ import annotations

import bisect
import sys
from collections import defaultdict
from typing import NamedTuple

import xplane

API_PREFIX = "api."
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
TUPLE = 13                       # xla.PrimitiveType.TUPLE


class Program(NamedTuple):
    op_names: dict               # {instruction: its metadata's op_name}
    outputs: int                 # buffers an execution returns


def install() -> None:
    """Have ``xplane.read`` attach the program's marks to its views."""
    if getattr(xplane.read, "adds_program_marks", False):
        return
    base = xplane.read

    def read(path: str):
        view = base(path)
        view.program_spans, view.programs_run = host_marks(path, view.window)
        view.programs = hlo_programs(path)
        return view

    read.adds_program_marks = True
    read.__wrapped__ = base
    xplane.read = read


# -- host spans ---------------------------------------------------------------

def host_marks(path: str, window) -> tuple:
    """The program's host spans (events named ``api.*`` off the device
    planes, with their attributes: the annotation's keyword arguments),
    and the programs whose ops ran on a host thread in ``window``."""
    from jax.profiler import ProfileData
    lo, hi = window
    spans, programs = [], set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(API_PREFIX):
                    spans.append((ev.name, ev.start_ns, ev.end_ns,
                                  dict(ev.stats)))
                elif ev.duration_ns > 0 and lo <= ev.start_ns < hi:
                    st = dict(ev.stats)
                    if "hlo_op" in st and "program_id" in st:
                        programs.add(f"{st['hlo_module']}({st['program_id']})")
    return sorted(spans, key=lambda s: s[1]), programs


# -- op names from the programs' HLO ------------------------------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one protobuf message, in order: an int
    for varint and fixed-width fields, a memoryview for length-delimited
    ones (strings, bytes, nested messages)."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not decoded")
        yield num, val


def _get(buf, num: int, default=None):
    for k, v in fields(buf):
        if k == num:
            return v
    return default


def _map_values(entries) -> list:
    """Values of a protobuf map's entries (key 1, value 2)."""
    return [_get(e, 2, b"") for e in entries]


def hlo_programs(path: str) -> dict:
    """``{program: Program}`` from the HLO of each program the trace holds
    (XSpace.planes[name == /host:metadata].event_metadata[*]: the
    program's name and its ``Hlo Proto`` stat)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for plane_no, plane in fields(space):
        if plane_no != 1:
            continue
        name, events, stats = None, [], []
        for k, v in fields(plane):
            if k == 2:
                name = bytes(v).decode()
            elif k == 4:
                events.append(v)
            elif k == 5:
                stats.append(v)
        if name != METADATA_PLANE:
            continue
        proto_stat = {_get(m, 1, 0) for m in _map_values(stats)
                      if bytes(_get(m, 2, b"")).decode() == HLO_PROTO_STAT}
        for md in _map_values(events):
            module, proto = None, None
            for k, v in fields(md):
                if k == 2:
                    module = bytes(v).decode()
                elif k == 5 and _get(v, 1, 0) in proto_stat:
                    proto = _get(v, 6)
            if module and proto is not None:
                out[module] = _program(proto)
    return out


def _program(hlo_proto) -> Program:
    """HloProto.hlo_module: ``computations[*].instructions[*]``'s
    ``{name: metadata.op_name}``, and the number of elements of
    ``host_program_shape.result`` (one where it is not a tuple)."""
    module = _get(hlo_proto, 1, b"")
    names = {}
    for comp in (v for k, v in fields(module) if k == 3):
        for inst in (v for k, v in fields(comp) if k == 2):
            name, op_name = None, ""
            for k, v in fields(inst):
                if k == 1:
                    name = bytes(v).decode()
                elif k == 7:
                    op_name = bytes(_get(v, 2, b"")).decode()
            names[name] = op_name
    result = _get(_get(module, 4, b""), 2, b"")
    outputs = (sum(k == 4 for k, _ in fields(result))
               if _get(result, 2, 0) == TUPLE else 1)
    return Program(names, outputs)


# -- device time by named scope ---------------------------------------------

def programs_of(view, module: str) -> list:
    """The programs an execution named ``module`` can be: on a TPU the
    program of that name (its XLA Modules names carry the id); on the
    CPU, whose names do not, the programs of that module whose ops ran
    in the window (``programs_run``) and whose HLO the trace holds."""
    progs = getattr(view, "programs", None) or {}
    if module in progs:
        return [progs[module]]
    return [progs[p] for p in sorted(getattr(view, "programs_run", ()))
            if p.rsplit("(", 1)[0] == module and p in progs]


def program_ops(view, module: str, ran) -> dict | None:
    """``{op: op_name}`` for the ops ``ran`` in executions named
    ``module``, from those of ``programs_of`` that hold them all; None
    if none does, or if two that do name one differently."""
    progs = [p.op_names for p in programs_of(view, module)
             if ran <= p.op_names.keys()]
    if not progs:
        return None
    out = {op: progs[0][op] for op in ran}
    if any(ops[op] != out[op] for ops in progs[1:] for op in ran):
        return None
    return out


def executions(view, module: str) -> list:
    """``(start_ns, end_ns, name)`` of the executions of ``module`` that
    start in the window, in order."""
    lo, hi = view.window
    return sorted((s, e, m) for m, s, e in view.modules
                  if (m == module or m.startswith(module + "("))
                  and lo <= s < hi)


def outputs(view, module: str) -> tuple | None:
    """Buffers each execution of ``module`` in the window returned, from
    its program's HLO: ``(mean, executions)``.  None where the module did
    not run, or where ``programs_of`` an execution gives no count or two
    different ones."""
    runs = executions(view, module)
    counts = []
    for name in (m for _, _, m in runs):
        got = {p.outputs for p in programs_of(view, name)}
        if len(got) != 1:
            return None
        counts.append(got.pop())
    if not counts:
        return None
    return sum(counts) / len(counts), len(counts)


def scope_ms(view, module: str, scopes) -> tuple | None:
    """Device time per execution of ``module`` in the window, by named
    scope: ``({scope: ms}, executions)``.  Each leaf op of the first
    device that starts inside an execution counts under the innermost of
    ``scopes`` on its ``op_name`` path (a fused op under its own).  None
    where the module did not run, where ``program_ops`` cannot name an op
    it ran, or where none of ``scopes`` is on the ops it ran.

    The part of the executions' time under none of ``scopes`` (ops with
    no scope or none at all, and gaps between ops) goes to stderr."""
    cache = view.__dict__.setdefault("_scope_ms", {})
    key = (module, tuple(scopes))
    if key not in cache:
        cache[key] = _scope_ms(view, module, tuple(scopes))
    return cache[key]


def _scope_ms(view, module, scopes):
    if not view.ops:
        return None
    lo, hi = view.window
    runs = executions(view, module)
    if not runs:
        return None
    starts = [s for s, _, _ in runs]
    ran = defaultdict(list)          # execution's module name -> leaf ops
    for name, s, e in xplane.leaf_ops(view.ops[0]):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            ran[runs[i][2]].append((name, s, e))
    per, named = defaultdict(int), set()
    for m, ops in ran.items():
        op_names = program_ops(view, m, {name for name, _, _ in ops})
        if op_names is None:
            return None
        for name, s, e in ops:
            inner = [p for p in op_names[name].split("/") if p in scopes]
            named.update(inner)
            if inner:
                per[inner[-1]] += min(e, hi) - s
    if not named:
        return None
    n = len(runs)
    out = {sc: per[sc] / n / 1e6 for sc in scopes}
    total = sum(e - s for s, e, _ in runs) / n / 1e6
    rest = total - sum(out.values())
    print(f"{module} per execution: {total:.6f} ms; "
          + ", ".join(f"{sc} {ms:.6f}" for sc, ms in out.items())
          + f"; under none of them {rest:.6f} ms "
          f"({100 * rest / total:.2f}%)", file=sys.stderr, flush=True)
    return out, n
