"""device_idle_pct: share of the traced window in which no op ran on the
device, in %; with several chips, the mean of their shares."""


def read(trace, cell):
    if trace.window_s <= 0 or not trace.busy_s:
        return None
    busy = sum(trace.busy_s) / len(trace.busy_s)
    return 100.0 * (1.0 - busy / trace.window_s)
