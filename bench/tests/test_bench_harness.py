"""The harness end to end on the CPU at a tiny size: a restored session
answers as the cold one did, a sound run is correct, and a run with the
timed path broken underneath is not."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import gen, harness
from bench.tests.tiny import tiny_cell

ROOT = Path(__file__).resolve().parents[2]
pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture
def cache(tmp_path, monkeypatch):
    """A cache of the test's own; the compile cache stays off."""
    monkeypatch.setattr(harness, "CACHE", tmp_path / "cache")
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    return tmp_path / "cache"


def _run(cell, seed=11, seconds=0.4, trace=False):
    lines = []
    res = harness.run(cell, seed, seconds, trace, t_start=0.0,
                      require_tpu=False, out=lines.append,
                      err=lambda s: None)
    assert json.loads(lines[-1]) == res
    return res


def test_restored_session_answers_as_the_cold_one(cache):
    cell = tiny_cell("alerts-1m.bulk")
    logs = []

    def log(**fields):
        logs.append(fields)

    cold, ds, was_cold = harness.open_session(cell.config, log)
    warm, _, was_warm = harness.open_session(cell.config, log)
    assert was_cold and not was_warm
    assert [f["phase"] for f in logs] == ["cold_build", "restore"]
    ops = gen.Stream(ds, cell.traffic, cell.config["values"], 4).cycles(5)
    got = []
    for s in (cold, warm):
        drv = harness.Caller(s, s.queries[0], s.alerts[0],
                             cell.config["session"]["ingest_batch"])
        for op in ops:
            drv.do(op)
        drv.barrier()
        got.append(drv)
    a, b = got
    assert len(a.reads) == len(b.reads) > 0
    for x, y in zip(a.reads, b.reads):
        assert x[2].tobytes() == y[2].tobytes()
    assert [(f[0], f[1].tolist(), f[2].tobytes()) for f in a.fired] == \
        [(f[0], f[1].tolist(), f[2].tobytes()) for f in b.fired]
    assert a.fired


@pytest.mark.parametrize("name", ["alerts-1m.bulk", "feed-mincut-120k.mixed"])
def test_sound_run_is_correct(cache, name):
    cell = tiny_cell(name)
    first = _run(cell)          # builds and saves
    res = _run(cell, seed=2**31 + 5)  # restores
    for r in (first, res):
        assert r["correct"] and r["failed"] == 0, r["checks"]
    want = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


def test_traced_run_reports_host_counters(cache):
    cell = tiny_cell("feed-mincut-120k.mixed")
    lines = []
    res = harness.run(cell, 11, 0.4, True, t_start=0.0, require_tpu=False,
                      out=lines.append, err=lambda s: None)
    assert res["correct"]
    # no device plane on the CPU: only host-clock and counter metrics
    assert {"host_update_ms", "ingest_stall_share"} <= set(res["metrics"])
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    assert "breakdown" in res
    # partial ring slots: the engine's frontier log counts sparse steps
    window = [json.loads(x) for x in lines if '"phase": "window"' in x][0]
    assert window["steps"]["write_sparse"] > 0


def test_untraced_run_leaves_the_engine_unwrapped(cache, monkeypatch):
    from repro.core.engine import EagrEngine

    cell = tiny_cell("alerts-1m.bulk")
    seen = []
    orig = EagrEngine.read_batch

    def read_batch(self, *a, **k):
        seen.append("write_rows" in vars(self))
        return orig(self, *a, **k)

    monkeypatch.setattr(EagrEngine, "read_batch", read_batch)
    assert _run(cell)["correct"]
    assert seen and not any(seen)
    seen.clear()
    assert _run(cell, seed=3, trace=True)["correct"]
    assert any(seen)


@pytest.mark.parametrize("name", ["alerts-1m.bulk", "feed-mincut-120k.mixed"])
def test_window_is_whole_cycles(cache, name):
    cell = tiny_cell(name)
    lines = []
    harness.run(cell, 12, 0.3, False, t_start=0.0, require_tpu=False,
                out=lines.append, err=lambda s: None)
    window = [json.loads(x) for x in lines if '"phase": "window"' in x][0]
    calls = sum(c["n"] for k, c in window["calls"].items() if k != "barrier")
    assert calls > 0 and calls % len(cell.traffic["cycle"]) == 0
    assert len(window["gc"]["collections"]) == 3


def test_compile_inside_the_window_is_not_correct(cache, monkeypatch):
    import jax

    cell = tiny_cell("alerts-1m.bulk")
    assert _run(cell)["correct"]
    orig = harness.Caller.barrier

    def barrier(self):
        jax.jit(lambda x: x * 3.0 + 1.0)(np.float32(2.0)).block_until_ready()
        return orig(self)

    monkeypatch.setattr(harness.Caller, "barrier", barrier)
    res = _run(cell, seed=5)
    assert not res["correct"]
    assert res["checks"]["window_compiles"]["value"] > 0


def _unchanged(orig):
    def write_rows(self, rows, vals, mask, *, n_live=None, active="auto"):
        self.state = self.state._replace(now=self.state.now + 1.0)
        self._now_host += 1.0
    return write_rows


def _half(orig):
    def write_rows(self, rows, vals, mask, *, n_live=None, active="auto"):
        mask = np.array(mask, bool)
        live = np.flatnonzero(mask)
        mask[live[len(live) // 2:]] = False
        return orig(self, rows, vals, mask, n_live=len(live) // 2,
                    active=active)
    return write_rows


def _answer(orig):
    def read_batch(self, base_ids, batch_size=None):
        out = np.array(orig(self, base_ids, batch_size=batch_size))
        out.reshape(-1)[0] += 1.0
        return out
    return read_batch


def _fired(orig):
    import dataclasses

    def to_batch(self, *a):
        b = orig(self, *a)
        return dataclasses.replace(b, values=b.values + 1.0)
    return to_batch


FAULTS = {
    "state_unchanged": ("repro.core.engine.EagrEngine", "write_rows",
                        _unchanged),
    "half_batch": ("repro.core.engine.EagrEngine", "write_rows", _half),
    "answer_altered": ("repro.core.engine.EagrEngine", "read_batch", _answer),
    "firing_altered": ("repro.streams.alerts.AlertSet", "_to_batch", _fired),
}


# each fault the cell can have: the min-cut feed carries no alert to alter,
# and neither cell exchanges anything between chips
CASES = [(n, f) for n in ("alerts-1m.bulk", "feed-mincut-120k.mixed")
         for f in sorted(FAULTS)
         if not (f == "firing_altered" and n.startswith("feed"))]


@pytest.mark.parametrize("name,fault", CASES)
def test_broken_timed_path_is_not_correct(cache, monkeypatch, name, fault):
    cell = tiny_cell(name)
    assert _run(cell)["correct"]  # cold build, sound
    mod, cls_name, make = FAULTS[fault]
    import importlib

    cls = getattr(importlib.import_module(mod.rsplit(".", 1)[0]),
                  mod.rsplit(".", 1)[1])
    monkeypatch.setattr(cls, cls_name, make(getattr(cls, cls_name)))
    res = _run(cell, seed=77)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_control_lower_precision_is_not_correct(cache, monkeypatch):
    """The program's bfloat16 path on values bfloat16 cannot hold."""
    cell = tiny_cell("feed-mincut-120k.mixed",
                     values={"low": 257, "high": 257})
    assert _run(cell)["correct"]
    monkeypatch.setenv("EAGR_SEGAGG_BF16", "1")
    res = _run(cell, seed=78)
    assert not res["correct"]
    assert res["checks"]["read_mismatches"]["value"] > 0


@pytest.mark.parametrize("name", ["alerts-1m.bulk", "feed-mincut-120k.mixed"])
def test_control_verdict_is_not_correct(cache, name):
    """``bench/control.py``: the reference held in bfloat16 in the
    program's place decides the result line, and comes out not correct;
    the program's own verdict is on the line before."""
    cell = tiny_cell(name, values={"low": 1, "high": 1} if "alerts" in name
                     else {"low": 300, "high": 1000})
    lines = []
    res = harness.run(cell, 13, 0.4, False, t_start=0.0, require_tpu=False,
                      control="bfloat16", out=lines.append,
                      err=lambda s: None)
    program = [json.loads(x) for x in lines if '"phase": "program"' in x][0]
    assert program["correct"]
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_every_metric_has_a_reader_and_every_cell_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(harness.load_metric(m["name"]).read)
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.traffic["cycle"] and cell.config["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def _cli(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "alerts-1m.bulk", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cli_refuses_to_run_without_a_tpu():
    p = _cli(ROOT, {})
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_cli_fails_with_nothing_but_the_benchmark(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
