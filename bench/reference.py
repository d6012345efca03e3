"""Plain references of the key-value semantics the store promises.

Nothing here imports the program or takes anything it made.

* ``ValueByRecord`` decides ``correct``: the value of every record id
  and whether it is live, with the acknowledged updates of each batch
  applied in batch order (the last write of a key wins).  The store may
  refuse an update (an out-of-place update finds no free slot in the
  key's segment); a refused update must change nothing.
* ``LaggingStore`` is the control: a plain key-value store over 16-byte
  keys put in the program's place, which acknowledges every write call
  at once but applies it only when the next write call arrives (a later
  flush).  That breaks the configurations' guarantee that an
  acknowledged write is seen by every later lookup, so a run with it in
  place has to come out not correct.
"""

from __future__ import annotations

import numpy as np


def last_wins(ids: np.ndarray, vals: np.ndarray):
    """(ids, vals) keeping only each id's last occurrence in batch order."""
    _, rev = np.unique(ids[::-1], return_index=True)
    keep = len(ids) - 1 - rev
    return ids[keep], vals[keep]


class ValueByRecord:
    """Value and liveness by record id; ids at or past ``len(vals)`` were
    never loaded and are absent."""

    def __init__(self, vals: np.ndarray):
        self.vals = np.array(vals, np.uint32)
        self.live = np.ones(len(vals), bool)

    def lookup(self, ids: np.ndarray):
        """(found, values) the store must answer for ``ids``."""
        inside = ids < len(self.vals)
        safe = np.where(inside, ids, 0)
        found = inside & self.live[safe]
        return found, np.where(found[:, None], self.vals[safe], 0)

    def update(self, ids: np.ndarray, vals: np.ndarray,
               acked: np.ndarray) -> np.ndarray:
        """Apply the acknowledged ops of one update batch; returns the
        acknowledgements that are wrong (of a record that is not live)."""
        live, _ = self.lookup(ids)
        ok = acked & live
        i, v = last_wins(ids[ok], vals[ok])
        self.vals[i] = v
        return acked & ~live


def check_calls(ref: ValueByRecord, calls) -> dict:
    """Replay the window's calls on ``ref`` in the order they were made
    and count the ops whose answer is wrong: a lookup's found flag or
    value, an update acknowledged for a record that is not live."""
    wrong = {"lookup": 0, "update": 0}
    for c in calls:
        if c.op == "lookup":
            found, vals = ref.lookup(c.ids)
            bad = (c.found != found) | (found & np.any(c.values != vals, -1))
        else:
            bad = ref.update(c.ids, c.vals, c.found)
        wrong[c.op] += int(bad.sum())
    return wrong


def _prefix(keys: np.ndarray) -> np.ndarray:
    """First 8 bytes of each 16-byte key, as one uint64."""
    keys = np.asarray(keys, np.uint32)
    return keys[:, 0].astype(np.uint64) | (keys[:, 1].astype(np.uint64) << 32)


class LaggingStore:
    """The control store (see the module docstring).  It indexes keys by
    their first 8 bytes and compares all 16; a key set whose prefixes
    collide is refused rather than answered wrongly.  Its state lives in
    the object; the table it hands back is a placeholder."""

    def __init__(self):
        self.order = self.prefix = self.keys = self.vals = self.live = None
        self.pending = None

    def create(self):
        return None

    def _flush(self):
        if self.pending is not None:
            idx, vals = self.pending
            self.vals[idx] = vals
            self.live[idx] = True
            self.pending = None

    def _find(self, keys):
        p = _prefix(keys)
        pos = np.minimum(np.searchsorted(self.prefix, p), len(self.prefix) - 1)
        idx = self.order[pos]
        hit = (self.prefix[pos] == p) & np.all(self.keys[idx] == keys, -1)
        return hit, idx

    def load(self, table, keys, vals, batch):
        keys = np.asarray(keys, np.uint32)
        p = _prefix(keys)
        self.order = np.argsort(p, kind="stable")
        self.prefix = p[self.order]
        if np.any(self.prefix[1:] == self.prefix[:-1]):
            raise ValueError("key prefixes collide: the control cannot index them")
        self.keys = keys
        self.vals = np.array(vals, np.uint32)
        self.live = np.ones(len(keys), bool)
        tail = np.arange(len(keys) - (len(keys) - 1) % batch - 1, len(keys))
        self.live[tail] = False                 # the last load call waits
        self.pending = (tail, self.vals[tail].copy())
        return table, np.ones(len(keys), bool)

    def lookup(self, table, keys):
        hit, idx = self._find(np.asarray(keys))
        found = hit & self.live[idx]
        return found, np.where(found[:, None], self.vals[idx], 0)

    def update(self, table, keys, vals):
        keys, vals = np.asarray(keys), np.asarray(vals, np.uint32)
        hit, idx = self._find(keys)
        ok = hit & self.live[idx]
        self._flush()
        i, v = last_wins(idx[ok], vals[ok])
        self.pending = (i, v)
        return table, ok
