"""Roofline share of the fused codec kernels in a training step: the least
HBM bytes the fused encode and decode must move for the model's gradient
(``counts.codec_min_bytes``), over the device time of their custom
calls, against the chip's HBM bandwidth.  Nothing to read when the trace
shows no such kernel."""
import counts
import devtrace


def read(r):
    secs = devtrace.op_seconds(r.trace, devtrace.is_codec_kernel)
    if secs <= 0 or not r.window.get("steps"):
        return None
    bits = int(r.traffic["codec"]["msg_bits"])
    byts = r.window["steps"] * counts.codec_min_bytes(
        counts.model_params(r.sizes), bits)
    return 100.0 * byts / (secs * float(r.peaks["hbm_bytes_per_s"]))
