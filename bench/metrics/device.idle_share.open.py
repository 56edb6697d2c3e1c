"""device.idle_share.open: share (%) of an open-loop traced window in
which no operation ran on the device (profiler trace)."""


def read(run):
    if run.trace is None or run.mix["loop"] != "open":
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
