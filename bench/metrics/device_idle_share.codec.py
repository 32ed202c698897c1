"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (100 x (1 - busy / window)).  Nothing to
read when the trace holds no device operation."""
import devtrace


def read(r):
    w = devtrace.window_s(r.trace)
    if not r.trace.chips() or w <= 0:
        return None
    return 100.0 * (1.0 - devtrace.mean_busy_s(r.trace) / w)
