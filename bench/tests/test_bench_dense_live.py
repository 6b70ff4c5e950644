"""The reader of ``dense_live_block_share``: the mean share of the padded
blocks the dense write steps of the window visit, from the ``eagr.put``
spans' arguments; nothing when no span carries them."""
import types

import pytest

from bench import harness
from bench import program_trace as pt

I = pt.Interval


def _ctx(spans):
    t = pt.ProgramTrace(ops={}, modules={}, spans=spans)
    ctx = harness.Context(
        cell=types.SimpleNamespace(name="alerts-1m.bulk"), ops=[],
        window_s=1.0, ingest={}, frontier=[], trace=t,
        window=I("window", 0, 1000), need_bytes=None, peaks={})
    vars(ctx)["program_trace"] = t
    return ctx


def _put(start, live=None, padded=None):
    args = (("batch", start),)
    if live is not None:
        args += (("live_blocks", live), ("padded_blocks", padded))
    return I("eagr.put", start, start + 10, args=args)


def test_share_is_the_mean_over_the_windows_dense_steps():
    spans = [I("window", 0, 1000),
             _put(100, 12, 128), _put(200, 36, 128),
             _put(300),                      # a read's put: no arguments
             _put(1200, 128, 128)]           # outside the window
    got = harness.load_metric("dense_live_block_share").read(_ctx(spans))
    assert got == pytest.approx(100.0 * (12 / 128 + 36 / 128) / 2)


def test_nothing_to_read_without_the_arguments():
    m = harness.load_metric("dense_live_block_share")
    assert m.read(_ctx([I("window", 0, 1000), _put(100)])) is None
    ctx = _ctx([])
    ctx.trace, ctx.window = None, None
    vars(ctx)["program_trace"] = None
    assert m.read(ctx) is None
