"""Session layer (``session.py``): host milliseconds per ``update`` call,
less the time that call spent blocked on the ingest ring's backpressure —
routing, frontier expansion, ``device_put`` and dispatch. Mean per call over
the window. Moves ``ops_per_s``."""


def read(ctx):
    ups = [o for o in ctx.ops if o.kind == "update"]
    if not ups:
        return None
    return 1e3 * sum(o.t1 - o.t0 - o.stall_s for o in ups) / len(ups)
