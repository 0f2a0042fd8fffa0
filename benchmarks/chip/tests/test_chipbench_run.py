"""End-to-end runs of the chip benchmark's cells at tiny sizes on the CPU
(kernels in interpret mode), and the command's refusals."""
import os
import shutil
import subprocess
import sys

import pytest

import chipbench_tiny as tiny

ROOT = tiny.CHIP.parents[1]
CMD = [sys.executable, "benchmarks/chip/run.py", "--workload",
       tiny.TRAIN, "--seed", "1", "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_command_refuses_without_tpu():
    p = subprocess.run(CMD, cwd=ROOT, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_command_refuses_in_a_bare_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(CMD, cwd=tmp_path, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "repro" in p.stderr                    # the program is not there


@pytest.mark.parametrize("trace", [False, True])
def test_train_cell_tiny(monkeypatch, trace):
    monkeypatch.setattr(tiny.harness, "peaks_for", lambda kind: tiny.PEAK)
    r = tiny.run(tiny.TRAIN, trace=trace)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] == 4                    # 1 warm-up + 3 in window
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"rmse_gap", "late_gain_gap", "eval_gap"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    if trace:
        assert {"train.pack_s", "train.schedule_s",
                "train.epoch_s"} <= set(r["metrics"])
        assert "breakdown" in r and "busy_s" in r["device"]
    else:
        assert set(r["metrics"]) == {"setup_s", "train_ratings_per_s",
                                     "heldout_rmse"}
        assert r["metrics"]["train_ratings_per_s"]["value"] > 0


@pytest.mark.parametrize("trace", [False, True])
def test_serve_cell_tiny(monkeypatch, trace):
    monkeypatch.setattr(tiny.harness, "peaks_for", lambda kind: tiny.PEAK)
    r = tiny.run(tiny.SERVE, seconds=2.0, trace=trace)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] == 300                  # 150 users/s for 2 s
    assert set(r["checks"]) == {"score_gap", "miss_share"}
    if trace:
        assert {"serve.queue_ms", "serve.flush_ms", "serve.p99_ms",
                "serve.index_s"} <= set(r["metrics"])
    else:
        assert set(r["metrics"]) == {"setup_s", "serve_p90_ms",
                                     "recall_at_10"}
        assert 0.0 < r["metrics"]["recall_at_10"]["value"] <= 1.0
