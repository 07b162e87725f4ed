"""Share of the roofline of the grouped-product kernels of the dropless
expert layer (Megablox gmm and tgmm, found by operand signature): the
FLOPs and bytes the window's routed rows need (``nemotron.gmm_need``,
from the program's ``moe_rows`` counter, not from padded tiles), at the
chip's peaks, over the kernels' device time in the trace."""

from bench.lib.nemotron import gmm_roofline


def read(ctx):
    return gmm_roofline(ctx)
