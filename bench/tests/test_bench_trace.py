"""The trace reduction and the roofline arithmetic, on a synthetic trace
and on a small trace recorded on the CPU."""
import json

import numpy as np
import pytest

from bench import trace_reduce as tr

I = tr.Interval
KERNEL_1 = "%segment_agg_call.3 = f32[256,128]{1,0} custom-call(s32[8]{0} %a)"
KERNEL_2 = "%segment_agg_call = f32[256,128]{1,0} custom-call(s32[8]{0} %b)"


def _synthetic() -> tr.Trace:
    dev = "/device:TPU:0"
    ops = [I("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 100, 200),
           I(KERNEL_1, 150, 300),
           I("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %segment_agg_call.3)",
             500, 600),
           I(KERNEL_2, 580, 700),
           I("%copy.3 = f32[8]{0} copy(f32[8]{0} %x)", 900, 1000)]
    modules = [I("jit_alert_write_step(12)", 100, 300),
               I("jit_write_step_sum_sparse(3)", 500, 700),
               I("jit_read_step_sparse(4)", 900, 1000)]
    spans = [I("window", 50, 1050), I("update", 60, 320),
             I("drain", 320, 480), I("read", 880, 1010),
             I("generate", 1010, 1040)]
    return tr.Trace(ops={dev: ops}, modules={dev: modules}, spans=spans)


def test_busy_is_the_union_of_device_intervals():
    t = _synthetic()
    w = t.window()
    assert w.start_ns == 50 and w.end_ns == 1050
    # 100-300, 500-700, 900-1000: 500 ns busy of 1000
    assert tr.busy_seconds(t, w) == pytest.approx(500e-9)
    assert tr.union(t.ops["/device:TPU:0"]) == [(100, 300), (500, 700),
                                                (900, 1000)]


def test_busy_is_clipped_to_the_window():
    t = _synthetic()
    assert tr.busy_seconds(t, I("window", 150, 550)) == pytest.approx(200e-9)


def test_per_program_device_time():
    t = _synthetic()
    w = t.window()
    n, s = tr.program_executions(
        t, w, (r"^jit_(_write_body|write_step|alert_write_step)",))
    assert n == 2 and s == pytest.approx(400e-9)
    n, s = tr.program_executions(t, w, (r"^jit_(_read_body|read_step)",))
    assert n == 1 and s == pytest.approx(100e-9)


def test_kernel_time_and_top_ops():
    from bench.metrics import segment_agg_roofline as m

    t = _synthetic()
    w = t.window()
    # the fusion that reads the kernel's output is not kernel time
    assert tr.kernel_seconds(t, w, m.KERNELS) == pytest.approx(270e-9)
    top = tr.top_ops(t, w, top=2)
    assert top[0][0] == "alert_write_step %segment_agg_call.3 custom-call"
    assert top[0][1] == pytest.approx(150e-9)
    assert top[1][0] == "write_step_sum_sparse %segment_agg_call custom-call"


def test_kernel_time_inside_write_programs():
    from bench.metrics import segment_agg_roofline as m

    dev = "/device:TPU:0"
    ops = [I(KERNEL_1, 100, 200), I(KERNEL_2, 400, 450)]
    modules = [I("jit_alert_write_step(12)", 90, 250),
               I("jit_read_step(4)", 380, 500)]
    t = tr.Trace(ops={dev: ops}, modules={dev: modules},
                 spans=[I("window", 0, 1000)])
    w = t.window()
    assert tr.kernel_seconds(t, w, m.KERNELS) == pytest.approx(150e-9)
    # the read step's reduction is not the write steps' kernel time
    assert tr.kernel_seconds(t, w, m.KERNELS, within=m.WRITE_PROGRAMS) \
        == pytest.approx(100e-9)


def test_idle_gaps_are_named_by_the_open_host_span():
    t = _synthetic()
    gaps = tr.idle_gaps(t, t.window())
    # gaps: 50-100 (update), 300-500 (drain: midpoint 400), 700-900
    # (no span open at 800), 1000-1050 (generate at 1025)
    assert gaps[0] == ["drain", pytest.approx(200e-9)]
    assert gaps[1] == ["none", pytest.approx(200e-9)]
    assert sorted(g[0] for g in gaps[2:]) == ["generate", "update"]


def test_reduce_bytes_counts_payload_and_index_not_padding():
    # 1,000 real edges of one f32 value and an int32 index, 10 rows written
    assert tr.reduce_bytes(1000, 10, 1) == 1000 * 8 + 10 * 4
    assert tr.reduce_bytes(1000, 10, 4) == 1000 * 20 + 10 * 16
    share = tr.roofline_share(819e6, 0.002, 819e9)
    assert share == pytest.approx(50.0)
    assert tr.roofline_share(1.0, 0.0, 819e9) is None


def test_peaks_are_looked_up_by_device_kind(tmp_path):
    p = tr.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        tr.peaks("TPU v9 imaginary")
    other = tmp_path / "peaks.json"
    other.write_text(json.dumps({"X": {"hbm_bytes_per_s": 1.0}}))
    assert tr.peaks("X", other)["hbm_bytes_per_s"] == 1.0


def test_recorded_cpu_trace_has_the_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.arange(1024.0)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("update"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    t = tr.load(tmp_path, ("window", "update"))
    w = t.window()
    ups = [s for s in t.spans if s.name == "update"]
    assert len(ups) == 3
    assert all(w.start_ns <= s.start_ns and s.end_ns <= w.end_ns for s in ups)
    # the CPU has no device plane: nothing is busy, no gap can be named
    assert t.ops == {} and tr.busy_seconds(t, w) == 0.0
    assert tr.idle_gaps(t, w) == []
    assert np.isfinite(w.dur_ns) and w.dur_ns > 0
