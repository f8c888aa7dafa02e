"""Share of the traced window in which no operation ran on the chip:
1 - (union of device-op intervals / window), from the profiler trace,
averaged over the chips that ran an operation."""


def read(run):
    return None if run.trace is None else run.trace.idle_share
