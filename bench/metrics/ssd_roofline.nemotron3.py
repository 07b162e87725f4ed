"""Share of the roofline of the SSD forward kernel (``kernels/ssd_scan``)
in the Nemotron-H stage (8 B/C groups, chunk 128): its FLOPs and bytes
from the operand shapes of each call in the trace
(``cost.ssd_forward_cost``), at the chip's peaks, over the kernel's
device time."""

from bench.lib.readers import ssd_roofline


def read(ctx):
    share, _ = ssd_roofline(ctx)
    return share
