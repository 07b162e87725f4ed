"""Model FLOP utilisation of training: forward + backward model FLOPs
per token from the configuration's shapes (``cost.mamba2_flops_per_token``,
the SSD chunk scan included, recomputation not counted) times the
traced window's tokens per second, over the chip's bf16 peak."""

from bench.lib.cost import mamba2_flops_per_token


def read(ctx):
    c = ctx["counters"]
    if not c.get("tokens") or not c.get("window_s") or ctx["peaks"] is None:
        return None
    rate = c["tokens"] / c["window_s"]
    flops = mamba2_flops_per_token(ctx["cell"].config["model"])
    n_chips = max(1, len(ctx["devices"] or ()))
    return 100.0 * flops * rate / (n_chips * ctx["peaks"]["bf16_flops"])
