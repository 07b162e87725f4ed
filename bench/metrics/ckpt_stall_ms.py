"""Mean time the trainer's call of ``CheckpointManager.save_async``
holds the step loop (the synchronous snapshot of the state to the
host), from the benchmark's own span around that call, over the saves
in the window."""


def read(ctx):
    d = ctx["spans"].get("bench.save") or []
    if not d:
        return None
    return 1e3 * sum(d) / len(d)
