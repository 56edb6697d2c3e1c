"""device.idle_share: share (%) of a closed-loop traced window in which no
operation ran on the device (1 - busy union / window, profiler trace)."""


def read(run):
    if run.trace is None or run.mix["loop"] != "closed":
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
