"""Load of the held experts: the largest held expert's rows over the
held experts' mean, per expert layer and step, summed over the traced
window (the program's ``moe_max_rows`` and ``moe_rows`` counters)."""

from bench.lib.nemotron import load_imbalance


def read(ctx):
    return load_imbalance(ctx)
