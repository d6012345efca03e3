"""Share of the HBM roofline that the update program reaches: the bytes a
batch of updates must move, over the program's device time times the
chip's HBM bandwidth (``peaks.json``).

Counted as for ``lookup_roofline_pct`` (the paper's layout, whatever
implements the op).  An update is out of place: it finds the key as a
lookup does, writes the new item into a free slot of the pair, and
commits with one store of the indicator/version word.  The two copies of
the table that the API's calls make are not required work and are not
counted: the share is meant to show them.
"""

from metrics.lookup_roofline_pct import (EXT_MAP, FP, INDICATOR, KEY,
                                         PAIR_SLOTS, VALUE)
from metrics.update_device_ms import MODULE


def op_bytes() -> int:
    """Per update: read the home pair's key row, its indicator/version
    word, fingerprint word and extension-map entry; write the new slot's
    key and value, the indicator/version word and the fingerprint word."""
    reads = PAIR_SLOTS * KEY + INDICATOR + FP + EXT_MAP
    writes = KEY + VALUE + INDICATOR + FP
    return reads + writes


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.module_runs(MODULE)
    if not runs:
        return None
    need = len(runs) * run.batch * op_bytes()
    return 100.0 * need / (sum(runs) / 1e9 * run.peaks["hbm_bytes_per_s"]), len(runs)
