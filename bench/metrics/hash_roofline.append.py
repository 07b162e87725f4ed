"""Share of the roofline of the checksum kernel at append
(``Log.complete*`` -> ``kernels/checksum``): the 12-byte seed and the
payload, unpadded, of every record appended in the traced window, at
819 GB/s, over the kernel's device time in the trace."""

from bench.lib.readers import hash_roofline


def read(ctx):
    return hash_roofline(ctx)
