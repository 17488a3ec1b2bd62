"""Share of the traced window in which no operation ran on the device, in
%, averaged over the cell's chips (profiler trace)."""


def read(run):
    if run.trace is None:
        return None
    return 100 * (1 - run.trace["busy_s"] / run.trace["window_s"])
