"""95th percentile, over every update op of the window, of the time from
the issue of its call to its result on the host (host clock)."""

import numpy as np

OP = "update"


def read(run):
    lat = [np.full(len(c.ids), c.t_done - c.t_issue)
           for c in run.calls if c.op == OP]
    if not lat:
        return None
    lat = np.concatenate(lat)
    return float(np.percentile(lat, 95)) * 1e3, len(lat)
