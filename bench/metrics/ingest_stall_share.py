"""Ingest ring (``streams/ingest.py``): the share of the window the caller
spent blocked on ring backpressure (``IngestStats.stall_s``), in per cent.
Moves ``ops_per_s``."""


def read(ctx):
    if "stall_s" not in ctx.ingest or ctx.window_s <= 0:
        return None
    return 100.0 * ctx.ingest["stall_s"] / ctx.window_s
