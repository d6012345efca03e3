"""Share of the HBM roofline that the lookup program reaches: the bytes a
batch of lookups must move, over the program's device time times the
chip's HBM bandwidth (``peaks.json``).

The bytes are the algorithm's, whatever implements the op (gather or
Pallas kernel), counted from the paper's table layout
(arXiv:2107.06836 Sec. III): buckets of 4 slots and 3 shared SBuckets per
bucket pair, so a pair's row holds (2 + 3) * 4 = 20 slots.  A copy of the
table is not required work and is not counted: the share is meant to
show it.
"""

from metrics.lookup_device_ms import MODULE

PAIR_SLOTS = 20      # key slots of one pair's row
KEY = 16             # bytes of a key
VALUE = 16           # bytes of a value
INDICATOR = 8        # indicator bits and version counter: one 8-byte word
FP = 8               # fingerprint word beside the indicator
EXT_MAP = 4          # the pair's extension-group index


def op_bytes() -> int:
    """Per lookup: the home pair's key row, its indicator/version word,
    its fingerprint word, its extension-map entry, and the one value
    returned."""
    return PAIR_SLOTS * KEY + INDICATOR + FP + EXT_MAP + VALUE


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.module_runs(MODULE)
    if not runs:
        return None
    need = len(runs) * run.batch * op_bytes()
    return 100.0 * need / (sum(runs) / 1e9 * run.peaks["hbm_bytes_per_s"]), len(runs)
