"""Tiny versions of the benchmark's cells, for tests on the CPU."""
from __future__ import annotations

import copy
import json

from bench import harness

SCALE = {"n_nodes": 600, "n_edges": 3600}

# The min-cut feed cell, left out of BENCHMARK.json until it is sized from a
# timing of its cold build on the chip's host (PERF.md, Open questions). Kept
# here at test size, so that the sum query under min-cut decisions and the
# frontier-sparse write and read steps that partial ring slots make stay
# tested through the harness.
FEED = {
    "config": {
        "name": "feed-mincut",
        "graph": {"generator": "powerlaw", "n_nodes": 120000,
                  "n_edges": 720000, "beta": 0.75, "sharing": 0.5,
                  "group": 16, "graph_seed": 0},
        "popularity": {"alpha": 1.0},
        "query": {"agg": "sum", "window": 8, "continuous": False,
                  "decisions": "mincut"},
        "values": {"low": 0, "high": 255},
        "session": {"ingest_depth": 2, "ingest_batch": 8192, "seed": 0},
    },
    "traffic": {
        "loop": "closed",
        "cycle": [{"op": "update", "events": 512},
                  {"op": "read", "readers": 512}],
        "warmup_cycles": 16,
        "block_cycles": 256,
        "compare_reads": 2048,
        "pregenerate_cycles": 64,
    },
    "end_to_end": ["ops_per_s", "read_p95_ms", "setup_s"],
    "per_layer": ["host_update_ms", "ingest_stall_share",
                  "write_step_device_ms", "read_step_device_ms",
                  "segment_agg_roofline", "device_idle_share"],
}
STAND_INS = {"feed-mincut-120k.mixed": FEED}


def load(name: str) -> harness.Cell:
    """Cell ``name`` of ``BENCHMARK.json``, or a stand-in of this file."""
    if name not in STAND_INS:
        return harness.load_cell(name)
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    s = copy.deepcopy(STAND_INS[name])

    def metrics(kind):
        return [dict(m, workloads=[name]) for m in spec[kind]
                if m["name"] in s[kind]]

    return harness.Cell(name, 1, s["config"], s["traffic"],
                        metrics("end_to_end"), metrics("per_layer"))


def tiny_cell(name: str, **config_changes) -> harness.Cell:
    """Cell ``name`` with its graph, ring slot and operation sizes cut so
    that a run takes seconds on a CPU. ``config_changes`` replace top-level
    blocks of the configuration."""
    cell = load(name)
    cfg = copy.deepcopy(cell.config)
    cfg["name"] += "-tiny"
    cfg["graph"].update(SCALE)
    bulk = "alerts" in name
    cfg["session"]["ingest_batch"] = 128 if bulk else 64
    cfg.update(copy.deepcopy(config_changes))
    traffic = copy.deepcopy(cell.traffic)
    for op in traffic["cycle"]:
        if "events" in op:
            op["events"] = 128 if bulk else 16
        if "readers" in op:
            op["readers"] = 32
    # sticky frontier widths grow until the warm-up has met the widest
    # partial slot: a window that meets a wider one compiles inside it
    traffic["warmup_cycles"] = 2 if bulk else 128
    traffic["block_cycles"] = 4
    cell.config, cell.traffic = cfg, traffic
    return cell
