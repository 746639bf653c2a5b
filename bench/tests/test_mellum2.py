"""The Mellum2 cell's own pieces on the CPU: its FLOP counts, its new
per-layer readers, and a whole run of the cell cut to a tiny size (the
program's SMOKE preset stands in for the published one, whose widths the
CPU cannot hold)."""
import json
import os
import shutil

import pytest

from conftest import BENCH, ROOT

TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, num_hidden_layers=4, moe_intermediate_size=32,
            num_experts=2, num_experts_per_tok=2, vocab_size=256,
            sliding_window=8, intermediate_size=128)


def _config():
    with open(os.path.join(BENCH, "configs", "mellum2-12b-ep4.json")) as f:
        return json.load(f)


def test_flops_count_the_window_and_the_held_picks():
    from harness import spec

    c = _config()
    model = spec.model_module(c)
    m = model.dims(c)
    assert (m["e"], m["E"], m["n_full"], m["n_sliding"]) == (16, 64, 7, 21)
    W = m["W"]
    for start, n in ((0, 700), (0, 3000), (1500, 600), (900, 300)):
        keys = sum(m["n_full"] * (p + 1) + m["n_sliding"] * min(p + 1, W)
                   for p in range(start, start + n))
        want = (2.0 * model.matmul_params(c) * n + 2.0 * m["d"] * m["V"]
                + 4.0 * m["hq"] * m["dh"] * keys)
        assert model.prefill_flops(c, start, n) == pytest.approx(want)
    short, long_ = model.decode_flops(c, 100), model.decode_flops(c, 5000)
    per_key = 4.0 * m["hq"] * m["dh"]
    assert long_ - short == pytest.approx(
        per_key * (7 * 4900 + 21 * (W - 100)))
    # Two of 16 held experts per token: 2 x 3 x d x f of expert weights.
    attn = 2304 * (32 + 8) * 128 + 32 * 128 * 2304
    assert model.matmul_params(c) == pytest.approx(
        28 * (attn + 2304 * 64 + 2 * 3 * 2304 * 896))


@pytest.mark.parametrize("name,num,den", [
    ("window_read_share", "decode_window_positions_read",
     "decode_window_positions_dense"),
    ("moe_useful_share", "moe_picks_held", "moe_rows_computed"),
])
def test_counter_shares(name, num, den):
    from harness import results, spec

    read = spec.metric_reader(name)

    def run(open_, close):
        return results.RunData(
            records=[], steps=None, t_open=0, t_close=1, t_last=1,
            setup_s=0, counters_open=open_, counters_close=close,
            pool_pages=1, chunk_steps=1, closed=True, config={}, model=None,
            peak_flops=1, kind_of=None)

    assert read(run({num: 10, den: 100}, {num: 35, den: 300})) == \
        pytest.approx(12.5)
    assert read(run({num: 5, den: 100}, {num: 5, den: 100})) is None
    assert read(run({}, {})) is None          # an engine without them


@pytest.fixture
def mellum_root(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    c = _config()
    c.update(TINY, layer_types=c["layer_types"][:4])
    c["published"]["num_experts"] = 8
    full = c["rope_parameters"]["full_attention"]
    full["original_max_position_embeddings"] = 16
    full.pop("attention_factor")
    c["deployment"].update(slots=4, max_len=96, page_size=4, pool_pages=64)
    c["check"] = {"max_logit_gap": 0.25}
    (bench / "configs" / "mellum2-12b-ep4.json").write_text(json.dumps(c))
    with open(bench / "traffic" / "agent.json") as f:
        t = json.load(f)
    t["arrivals"]["clients"] = 4
    t["prompt"].update(median=12, min=4, max=32)
    t["output"].update(median=24, min=16, max=48)
    (bench / "traffic" / "agent.json").write_text(json.dumps(t))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    from harness import cli, spec

    import repro.models as models
    from repro.models import registry

    real = registry.get_config

    def smoke(arch, smoke=False):
        return real(arch, smoke=smoke or arch.startswith("mellum2"))

    monkeypatch.setattr(models, "get_config", smoke)
    monkeypatch.setattr(spec, "ROOT", str(root))
    monkeypatch.setattr(spec, "BENCH", str(bench))
    v5e = cli.peaks_for("TPU v5 lite")
    monkeypatch.setattr(cli, "require_chips", lambda jax, n: jax.devices())
    monkeypatch.setattr(cli, "peaks_for", lambda kind: v5e)
    return root


def test_tiny_agent_cell_runs_correct(mellum_root, capsys):
    from harness import cli

    argv = ["--workload", "mellum2-12b-ep4.agent", "--seed",
            str(2 ** 33 + 7), "--seconds", "4", "--trace", "0"]
    assert cli.main(argv) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
    assert out["checks"]["checked_tokens_at_least"]["value"] > 0
