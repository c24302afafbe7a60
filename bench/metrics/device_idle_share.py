"""Device: share of the traced window in which no operation ran on the
device, 1 - (union of op intervals) / window, in % (profiler trace).  The
window runs from the first device operation to the end of the last
(``trace_reduce``)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
