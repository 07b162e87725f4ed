"""Share of the traced window in which no operation ran on the chip:
1 - busy / window, busy being the union of the device's op intervals."""

from bench.lib.readers import device_idle


def read(ctx):
    return device_idle(ctx)
