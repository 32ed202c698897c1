"""Roofline share of a whole aggregation call: the least HBM bytes of one
call whatever implements the draw (gradient in, aggregate out, packed
words out and back in; ``counts.codec_min_bytes``), times the calls
in the traced window, over the device's busy time in the window, against
the chip's HBM bandwidth."""
import counts
import devtrace


def read(r):
    busy = devtrace.mean_busy_s(r.trace)
    if busy <= 0 or not r.window.get("calls"):
        return None
    bits = int(r.traffic["codec"]["msg_bits"])
    byts = r.window["calls"] * counts.codec_min_bytes(r.cell.coords, bits)
    return 100.0 * byts / (busy * float(r.peaks["hbm_bytes_per_s"]))
