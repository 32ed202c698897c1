"""Model FLOP utilisation of the training step over the traced window:
the forward and backward FLOPs the model requires per token
(``counts.train_flops_per_token``, recomputation not counted) times the
tokens of the steps completed in the window, over the window's time,
the cell's chips and the chip's bf16 peak."""
import counts


def read(r):
    w = r.window
    if not w.get("tokens") or w["elapsed_s"] <= 0:
        return None
    flops = w["tokens"] * counts.train_flops_per_token(
        r.sizes, int(r.traffic["seq_len"]))
    peak = r.ctx.chips * float(r.peaks["bf16_flops_per_s"])
    return 100.0 * flops / (w["elapsed_s"] * peak)
