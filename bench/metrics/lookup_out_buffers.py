"""Device buffers one lookup call returns: the elements of the lookup
program's result, from the program's HLO that the profiler keeps in the
trace (``program_trace.outputs``), over the window's executions of it."""

import program_trace
from metrics.lookup_device_ms import MODULE

program_trace.install()


def read(run):
    if run.trace is None:
        return None
    return program_trace.outputs(run.trace, MODULE)
