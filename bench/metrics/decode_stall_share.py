"""Scheduler: share of the window in which resident decoders waited
behind chunk bursts (``ContinuousEngine.stats["stall_s"]``, chunk timing
synced on the device), in %."""


def read(run):
    return 100.0 * run.stats["stall_s"] / run.window_s
