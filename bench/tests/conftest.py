"""CPU tests of the benchmark harness: ``python -m pytest bench/tests``.

``tiny_root`` is a checkout-shaped directory holding BENCHMARK.json plus one
new configuration, traffic mix and per-layer metric added as files only,
next to copies of the benchmark's own files - the way a later change adds a
cell."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

# A limit holds for the size it was read at: the configuration's own is set
# from chip readings at its 40 layers, where the gaps are wider.  At these
# two sizes the program reads 0.008-0.039 and the float8 control 0.29-0.42
# (WIDE_DIMS, seeds 11-13, CPU), and 0.25 lies between.
TEST_CHECK = {"max_logit_gap": 0.25}
TINY_DIMS = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
                 vocab_size=256, dim_model_base=64, scale_depth=2 ** 0.5,
                 check=TEST_CHECK)
WIDE_DIMS = dict(num_hidden_layers=2, vocab_size=4096, scale_depth=2 ** 0.5,
                 check=TEST_CHECK)
NEW_METRIC = '''"""Requests due in the window (a metric added as a file)."""


def read(run):
    return float(len(run.records))
'''


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    return make_root(tmp_path, monkeypatch, TINY_DIMS)


@pytest.fixture
def wide_root(tmp_path, monkeypatch):
    """tiny.chat at minicpm-2b's published widths, with 2 layers and a
    small vocabulary: logits spread as at full size, so a lower precision
    shows in the gaps as it does on the chip."""
    return make_root(tmp_path, monkeypatch, WIDE_DIMS)


def make_root(tmp_path, monkeypatch, dims):
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    with open(bench / "configs" / "minicpm-2b.json") as f:
        cfg = json.load(f)
    cfg.update(dims)
    cfg["deployment"].update(slots=4, max_len=256, pool_pages=48,
                             warm_pages=12)
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    with open(bench / "traffic" / "sessions.json") as f:
        t = json.load(f)
    t.update(system={"count": 2, "tokens": 48}, max_prompt=160)
    t["arrivals"]["rate_per_s"] = 4.0
    t["think_s"]["mean"] = 0.2
    t["prompt"].update(median=16, min=4, max=48)
    t["output"].update(median=8, min=2, max=24)
    (bench / "traffic" / "tiny_chat.json").write_text(json.dumps(t))
    (bench / "metrics" / "requests_due.py").write_text(NEW_METRIC)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny", "source": "test", "why": "test",
                            "file": "bench/configs/tiny.json", "reduced": []})
    spec["workloads"].append({"name": "tiny.chat", "config": "tiny",
                              "traffic": "tiny_chat", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "minicpm-2b.sessions" in m.get("workloads", []):
            m["workloads"].append("tiny.chat")
    spec["per_layer"].append({"name": "requests_due", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves": "ttft_p90_s",
                              "workloads": ["tiny.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    from harness import spec as sp

    monkeypatch.setattr(sp, "ROOT", str(root))
    monkeypatch.setattr(sp, "BENCH", str(bench))
    return root
