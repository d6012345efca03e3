"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device op intervals, program (XLA module) executions, the
benchmark's own host spans, and device busy time within the window.

On a TPU the device planes are ``/device:TPU:<n>``: op executions on their
``XLA Ops`` line (a loop's event spans the events of its body, and an
event's name is its whole HLO instruction), program executions on their
``XLA Modules`` line, named ``<module>(<fingerprint>)``.  On
the CPU (the tests) ops run on host threads and carry ``hlo_op`` and
``hlo_module`` stats; a program execution is then the extent of the ops
that share one ``run_id``.  All times are nanoseconds on the trace's
clock, which the host spans share.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class TraceView:
    ops: list          # per device: [(name, start_ns, end_ns)]
    modules: list      # [(module name, start_ns, end_ns)] on the first device
    spans: list        # [(span name, start_ns, end_ns)] of the benchmark
    window: tuple      # (start_ns, end_ns) of the measured window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_intervals(self, ops) -> list:
        """Union of the op intervals of one device, clipped to the window."""
        lo, hi = self.window
        out = []
        for _, s, e in sorted(ops, key=lambda o: o[1]):
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices traced."""
        if not self.ops:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(d))
                   for d in self.ops) / len(self.ops) / 1e9

    def module_runs(self, name: str) -> list:
        """Durations (ns) of the executions of XLA module ``name`` inside
        the window; a TPU trace may suffix the name with ``(<id>)``."""
        lo, hi = self.window
        return [e - s for m, s, e in self.modules
                if (m == name or m.startswith(name + "(")) and lo <= s < hi]

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time in the window (leaf ops of
        the first device: a loop counts through its body), and the idle
        time of the first device split by the benchmark span the host was
        in (``outside any span`` where it was in none)."""
        lo, hi = self.window
        per_op = defaultdict(int)
        for name, s, e in leaf_ops(self.ops[0]) if self.ops else []:
            if min(e, hi) > max(s, lo):
                per_op[name] += min(e, hi) - max(s, lo)
        busy = self.busy_intervals(self.ops[0]) if self.ops else []
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        # the benchmark's spans inside the window follow one another
        inner = sorted((s for s in self.spans if s[0] != WINDOW_SPAN),
                       key=lambda s: s[1])
        ends = [s[2] for s in inner]
        idle = defaultdict(int)
        for g0, g1 in gaps:
            covered = 0
            for name, s, e in inner[bisect.bisect_right(ends, g0):]:
                if s >= g1:
                    break
                part = min(e, g1) - max(s, g0)
                idle[name[len(SPAN_PREFIX):]] += part
                covered += part
            idle["outside any span"] += (g1 - g0) - covered
        rank = lambda d: sorted(((k, v) for k, v in d.items() if v > 0),
                                key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, t / 1e9] for n, t in rank(per_op)],
                "idle_gaps": [[n, t / 1e9] for n, t in rank(idle)]}

    def host_spans(self) -> dict:
        """Seconds the host spent in each benchmark span in the window."""
        lo, hi = self.window
        out = defaultdict(int)
        for name, s, e in self.spans:
            if name != WINDOW_SPAN and lo <= s < hi:
                out[name[len(SPAN_PREFIX):]] += e - s
        return {k: v / 1e9 for k, v in out.items()}


def leaf_ops(ops) -> list:
    """The ops of one device that hold no other op inside them."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt[1] < o[2] and nxt[2] <= o[2])]


def short_name(hlo: str) -> str:
    """``%fusion.6 = s32[4096] fusion(...)`` -> ``fusion.6``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _stats(event) -> dict:
    return dict(event.stats)


def read(path: str) -> TraceView:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tpu = sorted((p for p in pd.planes if p.name.startswith("/device:TPU:")
                  and p.name[len("/device:TPU:"):].isdigit()),
                 key=lambda p: int(p.name.rsplit(":", 1)[1]))
    ops, modules, spans = [], [], []
    cpu_ops = defaultdict(list)          # run_id -> [(module, start, end)]
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
                elif not tpu and ev.duration_ns > 0:
                    st = _stats(ev)
                    if "hlo_op" in st:
                        cpu_ops[st.get("run_id")].append(
                            (st["hlo_module"], ev.name, ev.start_ns, ev.end_ns))
    if tpu:
        for i, plane in enumerate(tpu):
            dev_ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev_ops += [(short_name(e.name), e.start_ns, e.end_ns)
                                for e in line.events]
                elif line.name == "XLA Modules" and i == 0:
                    modules += [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
            ops.append(dev_ops)
    elif cpu_ops:
        ops.append([(n, s, e) for run in cpu_ops.values()
                    for _, n, s, e in run])
        for run in cpu_ops.values():
            modules.append((run[0][0], min(r[2] for r in run),
                            max(r[3] for r in run)))
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    return TraceView(ops, sorted(modules, key=lambda m: m[1]), spans,
                     (win[0][1], win[0][2]))


def read_dir(trace_dir: str) -> TraceView:
    """The trace ``jax.profiler.stop_trace`` wrote under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return read(files[0])
