"""Whole runs on the CPU at a tiny size, with the harness's look for a chip
skipped: the result line, a cell added as files only, refusal off-chip, and
``correct`` coming out false when the timed path is broken underneath."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT


def run(capsys, monkeypatch, *extra, trace=0):
    """A whole run on whatever device JAX has, read against v5e peaks."""
    from harness import cli

    v5e = cli.peaks_for("TPU v5 lite")
    monkeypatch.setattr(cli, "require_chips", lambda jax, n: jax.devices())
    monkeypatch.setattr(cli, "peaks_for", lambda kind: v5e)
    argv = ["--workload", "tiny.chat", "--seed", str(2 ** 33 + 5),
            "--seconds", "3", "--trace", str(trace), *extra]
    assert cli.main(argv) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1])


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "minicpm-2b.sessions", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    import shutil

    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "minicpm-2b.sessions",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_new_cell_from_files_only(tiny_root, capsys, monkeypatch):
    """tiny.chat: a configuration, a traffic mix and a per-layer metric that
    exist only as new files and BENCHMARK.json entries."""
    out = run(capsys, monkeypatch)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p50_s", "ttft_p90_s",
                                   "tpot_p90_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["worst_logit_gap"]["value"] <= \
        out["checks"]["worst_logit_gap"]["limit"]
    assert out["device"]["count"] == 1
    from harness import spec

    cell = spec.load_cell("tiny.chat")
    assert "requests_due" in [m["name"] for m in cell.per_layer]
    assert spec.metric_reader("requests_due") is not None


def _off_by_one(orig):
    def call(self, logits, keys=None):
        return (orig(self, logits, keys) + 1) % 256
    return call


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(fault, tiny_root, capsys,
                                          monkeypatch):
    from repro.models import common
    from repro.serve import sampling

    if fault == "token_altered":
        # Every sampled token moved to its neighbour where it is produced.
        monkeypatch.setattr(sampling.Sampler, "__call__",
                            _off_by_one(sampling.Sampler.__call__))
    else:
        # The decode/prefill step returns the KV pool unchanged.
        monkeypatch.setattr(common, "append_kv_paged",
                            lambda pool, new, lengths, seg, pages: pool)
    out = run(capsys, monkeypatch)
    assert out["correct"] is False
    gap = out["checks"]["worst_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_fails_where_the_program_passes(wide_root):
    """The float8 control, read over the streams the program served, fails
    the configuration's limit, which the program passes."""
    import control
    from harness import spec

    row = control.readings(spec.load_cell("tiny.chat"), 11, 3.0)
    assert row["tokens"] > 0 and row["bad_streams"] == 0
    assert row["control_worst_gap"] > 3 * row["program_worst_gap"]
    assert row["program_correct"] is True
    assert row["control_correct"] is False
