"""``ckpt_stall_ms`` in the Nemotron-H training cell: the mean time the
trainer's call of ``CheckpointManager.save_async`` holds the step loop,
from the benchmark's span around that call.  The cell's state is
donated to each step, so this call waits out the state's copy to the
host as well as starting it."""

from bench.lib.common import load_module


def read(ctx):
    return load_module("metrics", "ckpt_stall_ms").read(ctx)
