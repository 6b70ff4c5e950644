"""``chip_smoke.py`` end to end at a tiny size on the CPU backend: every
phase, reference check and write-path assertion of the one-chip run, minus
the TPU requirement, which ``main`` enforces."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402


def test_smoke_phases_pass_at_tiny_size(capsys):
    chip_smoke.run_single(5_000, 50_000, mincut_nodes=3_000,
                          mincut_edges=18_000, ingest_batch=1_024,
                          on_chip=False)
    out = capsys.readouterr().out
    assert '"phase": "live/drain_fired"' in out
    assert '"phase": "summary"' in out


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
