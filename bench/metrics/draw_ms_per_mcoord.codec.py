"""Device time of the shared randomness (the per-coordinate DECOMPOSE
draw and the dither): the device's busy time in the traced window outside
the two fused codec kernels, in milliseconds per 2^20 coordinates
aggregated."""
import devtrace


def read(r):
    kernels = devtrace.op_seconds(r.trace, devtrace.is_codec_kernel)
    busy = devtrace.mean_busy_s(r.trace)
    coords = r.window.get("coords")
    if not coords or kernels <= 0:
        return None
    return 1000.0 * (busy - kernels) / (coords / 2**20)
