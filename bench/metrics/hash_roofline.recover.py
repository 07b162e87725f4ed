"""Share of the roofline of the checksum kernel in the recovery scan
(``Log.open`` -> ``_first_bad_payload``): the unpadded bytes of every
record each open validated, at 819 GB/s, over the kernel's device
time in the trace."""

from bench.lib.readers import hash_roofline


def read(ctx):
    return hash_roofline(ctx)
