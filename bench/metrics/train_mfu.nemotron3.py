"""Model FLOP utilisation of the Nemotron-H stage's training: forward +
backward model FLOPs of the traced window, the routed experts counted at
the rows the held experts actually computed (the program's ``moe_rows``
counter), the rest per token from the configuration's shapes
(``nemotron.window_model_flops``; recomputation not counted), per second
of the window, over the chip's bf16 peak."""

from bench.lib.nemotron import train_mfu


def read(ctx):
    return train_mfu(ctx)
