"""Records per ingest wave over the window: Δacked ÷ Δwaves of
``IngestEngine.stats()`` (layer: ingest, ``core/ingest.py``)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("waves"):
        return None
    return c["acked"] / c["waves"]
