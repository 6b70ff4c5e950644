"""The reduction of the program's own scopes and spans, on a synthetic trace
and on a small trace recorded on the CPU, and the readers of the metrics
that read them."""
import dataclasses
import types

import pytest

from bench import harness
from bench import program_trace as pt
from bench import trace_reduce as tr

I = pt.Interval
DEV = "/device:TPU:0"
G = "jit(alert_write_step)/while/body/closed_call/eagr.level_gather/gather"
P = "jit(alert_write_step)/while/body/eagr.lane_pad/jit(_pad)/pad"
K = "jit(alert_write_step)/eagr.segment_agg/jit(segment_agg_call)/x"
A = "jit(alert_write_step)/eagr.alert_eval/reduce_sum"
R = "jit(read_step)/eagr.read_step/while/body/eagr.level_gather/gather"

NEW = ("level_gather_device_ms", "lane_pad_device_ms",
       "alert_eval_device_ms", "host_read_ms", "fired_readback_ms",
       "fired_overflow_share")


def _synthetic() -> pt.ProgramTrace:
    ops = [
        # step 1 (100-400): a while loop enclosing a gather, a pad and the
        # kernel; the alert evaluation after it; one unscoped copy
        I("%while.57 = (s32[]) while(%t), body=%b", 100, 380, scope=""),
        I("%fusion.63 = f32[8]{0} fusion(%p)", 100, 200, scope=G),
        I("%pad.54 = f32[8,128]{1,0} pad(%x, %c)", 200, 250, scope=P),
        I("%segment_agg_call_sum.9 = f32[8,128] custom-call(%a)", 250, 380,
          scope=K),
        I("%fusion.11 = pred[8]{0} fusion(%m)", 380, 395, scope=A),
        I("%copy.116 = f32[8]{0} copy(%y)", 395, 400, scope=""),
        # step 2 (600-900), partly outside the window (ends at 800)
        I("%fusion.63 = f32[8]{0} fusion(%p)", 600, 700, scope=G),
        I("%pad.54 = f32[8,128]{1,0} pad(%x, %c)", 750, 850, scope=P),
        # the read step's own gather: not a write step's
        I("%fusion.7 = f32[8]{0} fusion(%q)", 950, 990, scope=R),
    ]
    modules = [I("jit_alert_write_step(1)", 100, 400),
               I("jit_alert_write_step(1)", 600, 900),
               I("jit_read_step(2)", 950, 1000)]
    spans = [
        I("window", 50, 800),
        I("eagr.update", 60, 90), I("eagr.ring_wait", 70, 80),
        I("eagr.read", 410, 470), I("eagr.read_route", 410, 420),
        I("eagr.read_wait", 440, 465),
        I("eagr.read", 500, 540), I("eagr.read_wait", 510, 520),
        I("eagr.drain", 540, 590),
        I("eagr.fired_readback", 545, 565, args=(("batch", 3),
                                                 ("overflow", 1))),
        I("eagr.fired_readback", 570, 580, args=(("batch", 4),
                                                 ("overflow", 0))),
        # outside the window: not read
        I("eagr.fired_readback", 810, 900, args=(("batch", 5),
                                                 ("overflow", 1))),
        I("eagr.read", 900, 1100), I("eagr.read_wait", 950, 1000),
    ]
    return pt.ProgramTrace(ops={DEV: ops}, modules={DEV: modules},
                           spans=spans)


def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _msg(*fields) -> bytes:
    """A protobuf message of ``(field number, int | bytes | str)``."""
    out = b""
    for no, v in fields:
        if isinstance(v, int):
            out += _varint(no << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(no << 3 | 2) + _varint(len(v)) + v
    return out


def test_scope_paths_are_read_from_the_trace_files_event_metadata(tmp_path):
    fusion = "%fusion.63 = f32[8]{0} fusion(%p), kind=kLoop"
    # an XSpace: a device plane whose event metadata carry ``tf_op`` (as a
    # string, or as a reference to a stat metadata name), and a host plane
    stats = [_msg((1, 1), (2, "tf_op")), _msg((1, 2), (2, "hlo_category")),
             _msg((1, 3), (2, P))]
    events = [_msg((1, 10), (2, fusion), (5, _msg((1, 2), (5, "fusion"))),
                   (5, _msg((1, 1), (5, G)))),
              _msg((1, 11), (2, "%pad.54 = f32[8,128] pad(%x)"),
                   (5, _msg((1, 1), (7, 3)))),
              _msg((1, 12), (2, "%copy.1 = f32[8] copy(%y)"))]
    plane = _msg((1, 7), (2, DEV),
                 *[(4, _msg((1, 10 + k), (2, e))) for k, e in
                   enumerate(events)],
                 *[(5, _msg((1, 1 + k), (2, m))) for k, m in
                   enumerate(stats)])
    host = _msg((2, "/host:CPU"), (5, _msg((1, 1), (2, _msg((1, 1),
                                                              (2, "x"))))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, plane), (1, host)))
    got = pt.op_paths(path)
    assert got == {DEV: {fusion: G, "%pad.54 = f32[8,128] pad(%x)": P}}
    iv = I("%x = f32[] add(%a, %b)", 0, 1, scope=R)
    assert iv.scopes == ("eagr.read_step", "eagr.level_gather")
    assert I("%y = f32[] add(%a, %b)", 0, 1, scope="jit(f)/add").scopes == ()


@pytest.mark.parametrize("name,opcode,leaf", [
    ("%while.57 = (s32[], f32[8]{0}) while(%tuple), body=%b", "while",
     False),
    ("%conditional.2 = f32[] conditional(%p, %a, %b)", "conditional", False),
    ("%call.4 = f32[8]{0} call(%a), to_apply=%f", "call", False),
    ("%fusion.63 = f32[8]{0} fusion(%p), kind=kLoop", "fusion", True),
    ("%segment_agg_call_sum.9 = f32[8,128] custom-call(%a)", "custom-call",
     True),
    ("%while.57", "while", False),
])
def test_control_flow_ops_are_not_leaves(name, opcode, leaf):
    iv = I(name, 0, 1)
    assert pt.opcode(iv) == opcode and pt.is_leaf(iv) is leaf


def test_scope_time_counts_leaf_ops_in_write_steps_clipped_to_window():
    t = _synthetic()
    w = t.window()
    by = pt.scope_seconds(t, w)
    # the while loop encloses its body: never counted; step 2's pad is cut
    # at the window's end (750-800); the read step is not a write step
    assert by["eagr.level_gather"] == pytest.approx(200e-9)
    assert by["eagr.lane_pad"] == pytest.approx(100e-9)
    assert by["eagr.segment_agg"] == pytest.approx(130e-9)
    assert by["eagr.alert_eval"] == pytest.approx(15e-9)
    assert by[""] == pytest.approx(5e-9)
    # two write-step executions begin in the window
    assert pt.scope_ms_per_step(t, w, "eagr.level_gather") == \
        pytest.approx(1e3 * 200e-9 / 2)
    # a scope the trace holds, but not in a write step; one it lacks
    assert pt.scope_ms_per_step(t, w, "eagr.read_step") == 0.0
    assert pt.scope_ms_per_step(t, w, "eagr.apply_writes") is None
    assert pt.uncovered_ops(t, w) == [["%copy.116 copy",
                                       pytest.approx(5e-9)]]


def test_host_spans_reduce_to_means_and_shares():
    t = _synthetic()
    w = t.window()
    # reads 410-470 (less its wait 25) and 500-540 (less 10): 35 and 30 ns
    assert pt.self_ms(t, w, "eagr.read", "eagr.read_wait") == \
        pytest.approx(1e-6 * 32.5)
    assert pt.self_ms(t, w, "eagr.update", "eagr.ring_wait") == \
        pytest.approx(1e-6 * 20)
    assert pt.mean_ms(t, w, "eagr.fired_readback") == pytest.approx(15e-6)
    assert pt.arg_share(t, w, "eagr.fired_readback", "overflow") == 50.0
    # idle gaps are named by the innermost span open at their midpoint:
    # 400-600 (500: the second read), 50-100 (75: the ring wait inside an
    # update), 700-750 (725: none)
    gaps = pt.idle_gaps(t, w)
    assert [g[0] for g in gaps] == ["eagr.read", "eagr.ring_wait", "none"]
    assert gaps[0][1] == pytest.approx(200e-9)


def _ctx(trace, program_trace, name="alerts-1m.bulk"):
    ctx = harness.Context(
        cell=types.SimpleNamespace(name=name), ops=[], window_s=1.0,
        ingest={}, frontier=[], trace=trace,
        window=trace.window() if trace is not None else None,
        need_bytes=None, peaks={})
    vars(ctx)["program_trace"] = program_trace
    return ctx


def test_every_new_reader_reads_the_program_trace():
    t = _synthetic()
    got = {m: harness.load_metric(m).read(_ctx(t, t)) for m in NEW}
    assert got["level_gather_device_ms"] == pytest.approx(1e-4)
    assert got["lane_pad_device_ms"] == pytest.approx(5e-5)
    assert got["alert_eval_device_ms"] == pytest.approx(7.5e-6)
    assert got["host_read_ms"] == pytest.approx(3.25e-5)
    assert got["fired_readback_ms"] == pytest.approx(1.5e-5)
    assert got["fired_overflow_share"] == 50.0


def test_new_readers_find_nothing_in_a_program_without_instrumentation():
    t = _synthetic()
    bare = pt.ProgramTrace(
        ops={DEV: [dataclasses.replace(iv, scope="") for iv in t.ops[DEV]]},
        modules=t.modules,
        spans=[s for s in t.spans if not s.name.startswith("eagr.")])
    for m in NEW:
        assert harness.load_metric(m).read(_ctx(t, bare)) is None, m
    # an untraced run, and a traced run that left no trace file
    for m in NEW:
        assert harness.load_metric(m).read(_ctx(None, None)) is None, m


def test_for_context_loads_the_cells_trace_once(tmp_path, monkeypatch):
    import jax

    monkeypatch.setattr(harness, "CACHE", tmp_path)
    where = tmp_path / "trace" / "cell-x"
    f = jax.jit(lambda x: x + 1.0)
    f(1.0).block_until_ready()
    jax.profiler.start_trace(str(where))
    try:
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("eagr.read", batch=7):
                with jax.profiler.TraceAnnotation("eagr.read_wait"):
                    f(2.0).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    t = tr.load(where, ("window",))
    ctx = harness.Context(
        cell=types.SimpleNamespace(name="cell-x"), ops=[], window_s=1.0,
        ingest={}, frontier=[], trace=t, window=t.window(),
        need_bytes=None, peaks={})
    got = pt.for_context(ctx)
    assert pt.for_context(ctx) is got
    reads = got.named("eagr.read", ctx.window)
    assert len(reads) == 1 and reads[0].arg("batch") == 7
    assert harness.load_metric("host_read_ms").read(ctx) > 0
    # the CPU has no device plane: the scope readers find nothing
    assert harness.load_metric("level_gather_device_ms").read(ctx) is None
    missing = _ctx(t, None, name="no-such-cell")
    del vars(missing)["program_trace"]
    assert pt.for_context(missing) is None
