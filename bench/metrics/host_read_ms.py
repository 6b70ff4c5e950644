"""Session (``session.py``): host milliseconds per ``read``, from the
program's spans: each ``eagr.read`` span less the ``eagr.read_wait`` (the
``device_get`` of the answer) inside it: the reader-scope check, routing,
frontier expansion, ``device_put``, dispatch and bookkeeping. Mean per read
over the window. Moves ``read_p95_ms``."""

from bench import program_trace as pt


def read(ctx):
    return pt.self_ms(pt.for_context(ctx), ctx.window, "eagr.read",
                      "eagr.read_wait")
