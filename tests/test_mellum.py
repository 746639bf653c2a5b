"""Mellum2 (MoE in every layer, 3:1 sliding-window/full attention, YaRN on
the full layers) through the model and ``ServeEngine`` against the plain
float32 reference (``repro.models.reference``), at the SMOKE size on the
CPU: one period of four layers, window 8, pages of 4, 8 experts top-2.

Tolerances: both sides compute in float32 and differ only in summation
order (the blocked online softmax, the ring's row order, the experts'
joint contraction), a few float32 ulps of logits of size ~1; 1e-4 leaves
room for that and none for a wrong mask, frequency or gate, each of which
moves logits by 1e-2 or more (the sensitivity tests show by how much).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FULL, SLIDING
from repro.models import build_model, get_config
from repro.models import common as cm
from repro.models import reference
from repro.serve.engine import Request, ServeEngine

TOL = 1e-4


def _cfg(layout="paged", **kw):
    return dataclasses.replace(get_config("mellum2-12b", smoke=True),
                               cache_layout=layout, **kw)


def _params(cfg, seed=0):
    return build_model(cfg).init(jax.random.PRNGKey(seed))


def _ref_logits(params, cfg, tokens):
    return np.asarray(reference.forward(params, np.asarray(tokens), cfg))


def test_smoke_has_both_layer_kinds_and_eight_experts():
    cfg = get_config("mellum2-12b", smoke=True)
    assert set(cfg.layer_period) == {FULL, SLIDING}
    assert cfg.n_experts >= 8 and cfg.rope_yarn is not None
    full = get_config("mellum2-12b")
    assert full.layer_period == (SLIDING, SLIDING, SLIDING, FULL)
    assert (full.n_layers, full.sliding_window, full.d_expert) == (28, 1024, 896)
    share = get_config("mellum2-12b-ep4")
    assert (share.experts_held, share.expert_shards, share.expert_shard) == (
        16, 4, 0)
    assert share.n_experts == 64 and share.experts_per_token == 8


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_prefill_decode_logits_match_reference(layout):
    """Ragged prefill (3, 13 and 21 tokens: inside, past and far past the
    window of 8), then 12 decode steps; every step's logits against the
    reference's full forward at the same position."""
    cfg = _cfg(layout)
    model = build_model(cfg)
    params = _params(cfg)
    rng = np.random.default_rng(0)
    lens = [3, 13, 21]
    steps = 12
    seqs = [rng.integers(0, cfg.vocab, n + steps).astype(np.int32)
            for n in lens]
    b, width = len(lens), max(lens)
    cache = model.init_cache(params, batch=b, max_len=48)
    if layout == "paged":
        n = cache["pages"].size
        cache["pages"] = jnp.arange(n, dtype=jnp.int32)[::-1].reshape(
            cache["pages"].shape)
    toks = np.zeros((b, width), np.int32)
    for r, (s, n) in enumerate(zip(seqs, lens)):
        toks[r, :n] = s[:n]
    got, cache = model.prefill(params, cache, jnp.asarray(toks),
                               seg_lens=jnp.asarray(lens, jnp.int32),
                               all_logits=True)
    refs = [_ref_logits(params, cfg, s) for s in seqs]
    for r, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(got[r, :n]), refs[r][:n],
                                   atol=TOL, rtol=0)
    for t in range(steps):
        step = np.asarray([s[n + t] for s, n in zip(seqs, lens)], np.int32)
        got, cache = model.decode_step(params, cache,
                                       jnp.asarray(step)[:, None])
        for r, n in enumerate(lens):
            np.testing.assert_allclose(np.asarray(got[r, 0]),
                                       refs[r][n + t], atol=TOL, rtol=0)


def _serve(cfg, params, prompts, budgets, slots=3, **kw):
    eng = ServeEngine(cfg, params, batch_slots=slots, max_len=64,
                      chunk_size=4, **kw)
    reqs = [Request(prompt=p, max_new_tokens=m)
            for p, m in zip(prompts, budgets)]
    eng.run(reqs)
    return eng, reqs


def _prompts(cfg, lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]


def _worst_gap(params, cfg, reqs):
    """Largest distance of a served token's reference logit below the
    reference's best at its position (0 where the served token is the
    reference's argmax)."""
    worst = 0.0
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1],
                                                   np.int32)])
        ref = _ref_logits(params, cfg, seq)[:, :cfg.vocab]
        rows = len(r.prompt) - 1 + np.arange(len(r.generated))
        gaps = ref[rows].max(-1) - ref[rows, r.generated]
        worst = max(worst, float(gaps.max()))
    return worst


def test_served_streams_match_reference():
    """Prefill then chunked decode through ServeEngine, ragged slots and
    re-admission into freed slots, contexts from 3 to 40 positions (the
    window is 8): every served token is the reference's best there."""
    cfg = _cfg()
    params = _params(cfg)
    prompts = _prompts(cfg, (5, 9, 14, 23, 2))
    eng, reqs = _serve(cfg, params, prompts, (12, 7, 20, 17, 9))
    assert all(r.status == "finished" for r in reqs)
    assert _worst_gap(params, cfg, reqs) <= TOL


@pytest.mark.parametrize("kernel", ["pallas_paged", "pallas_gather"])
def test_decode_kernels_serve_the_window_ring(kernel):
    """The decode kernels read the window ring in place (interpret mode
    here): the same streams as the XLA path."""
    cfg = _cfg()
    params = _params(cfg)
    prompts = _prompts(cfg, (5, 13, 3))
    _, want = _serve(dataclasses.replace(cfg, decode_kernel="xla"), params,
                     prompts, (14, 9, 12))
    _, got = _serve(dataclasses.replace(cfg, decode_kernel=kernel), params,
                    prompts, (14, 9, 12))
    assert [r.generated for r in got] == [r.generated for r in want]


@pytest.mark.parametrize("what", ["window", "yarn"])
def test_reference_sees_window_and_yarn(what):
    """The comparison is sensitive to both mechanisms: a reference without
    the window, or with plain RoPE on the full layers, misses the
    program's logits by far more than TOL."""
    cfg = _cfg()
    params = _params(cfg)
    seq = _prompts(cfg, (30,))[0]
    program = np.asarray(build_model(cfg).forward(params, seq[None])[0][0])
    np.testing.assert_allclose(program, _ref_logits(params, cfg, seq),
                               atol=TOL, rtol=0)
    other = (dataclasses.replace(cfg, sliding_window=1 << 20)
             if what == "window" else dataclasses.replace(cfg, rope_yarn=None))
    assert np.abs(program - _ref_logits(params, other, seq)).max() > 100 * TOL


def test_four_expert_shares_add_up_to_the_layer():
    """Each of 4 shards routes over all 8 experts and computes its 2; the
    shares' outputs add up to the uncut layer (and to the reference's), and
    every pick lands on exactly one shard."""
    cfg = dataclasses.replace(_cfg(), expert_shards=1)
    p = cm.moe_init(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, cfg.d_model))
    whole, _, picks = cm.apply_moe(p, x, cfg)
    assert int(picks) == 2 * 24 * cfg.experts_per_token
    total, total_picks = jnp.zeros_like(whole), 0
    for shard in range(4):
        c = dataclasses.replace(cfg, expert_shards=4, expert_shard=shard)
        held = slice(shard * c.experts_held, (shard + 1) * c.experts_held)
        part = {"router": p["router"],
                **{k: p[k][held] for k in ("wg", "wu", "wd")}}
        y, _, n = cm.apply_moe(part, x, c)
        total, total_picks = total + y, total_picks + int(n)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5, rtol=0)
    assert total_picks == int(picks)
    with jax.default_matmul_precision("highest"):
        ref = jax.vmap(lambda h: reference._moe(p, h, cfg))(x)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(ref),
                               atol=1e-5, rtol=0)


def test_moe_rows_go_through_experts_in_blocks(monkeypatch):
    """A wide block (a prefill wave) goes through the held experts a block
    of rows at a time, with the same result."""
    cfg = _cfg()
    p = cm.moe_init(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 20, cfg.d_model))
    whole = cm.apply_moe(p, x, cfg)[0]
    monkeypatch.setattr(cm, "_MOE_ROW_BLOCK", 16)
    blocked = cm.apply_moe(p, x, cfg)[0]
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=1e-6, rtol=0)


@pytest.mark.parametrize("max_len", [32, 128])
def test_window_state_bounded_by_the_window(max_len):
    """A slot's sliding layers hold exactly W positions each, whatever
    max_len or the context served; only the full layers' K/V are in the
    paged pool that admission gates on."""
    cfg = _cfg()
    params = _params(cfg)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=max_len,
                      chunk_size=4)
    W, psz = cfg.sliding_window, cfg.kv_page_size
    hd = cfg.n_kv_heads * cfg.head_dim_
    n_sliding = cfg.layer_types.count(SLIDING)
    ring = eng.cache["window"]["k"]
    assert ring.shape == (n_sliding, 2 * W // psz, psz, hd)
    assert ring.shape[1] * psz // 2 <= W + psz       # per slot: W <= W + page
    assert eng.cache["layers"]["k"].shape[0] == cfg.layer_types.count(FULL)
    r = Request(prompt=_prompts(cfg, (max_len // 2,))[0],
                max_new_tokens=max_len // 2)
    eng.run([r])
    assert r.status == "finished"
    assert eng.cache["window"]["k"].shape == ring.shape


def test_window_ring_holds_the_last_window_positions():
    """After a prefill past the window and some decode, ring rows
    [0, min(len, W)) of each slot hold exactly its last min(len, W)
    positions' keys (position p at row p % W)."""
    cfg = _cfg()
    model = build_model(cfg)
    params = _params(cfg)
    W, psz = cfg.sliding_window, cfg.kv_page_size
    seq = _prompts(cfg, (19,))[0]
    cache = model.init_cache(params, batch=1, max_len=32)
    cache["pages"] = jnp.arange(cache["pages"].size,
                                dtype=jnp.int32).reshape(cache["pages"].shape)
    _, cache = model.prefill(params, cache, jnp.asarray(seq[None, :15]))
    for t in range(15, 19):
        _, cache = model.decode_step(params, cache,
                                     jnp.asarray(seq[None, t:t + 1]))
    # Keys of the first sliding layer, recomputed from the embeddings.
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    h = cm.apply_norm(lp["ln1"], params["embed"]["tok"][seq][None], cfg)
    k = cm.rope(jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wk"]),
                jnp.arange(19), cfg.rope_theta)[0]
    ring = cm.gather_pages(cache["window"]["k"][0],
                           cm.ring_table(1, W, psz))[0]
    for p in range(19 - W, 19):
        np.testing.assert_allclose(np.asarray(ring[p % W]),
                                   np.asarray(k[p]).reshape(-1), atol=1e-5)


@pytest.mark.parametrize("knob", [dict(prefix_sharing=True),
                                  dict(spec_k=2),
                                  dict(kv_integrity=True)])
def test_window_configs_refuse_what_rings_cannot_do(knob):
    cfg = _cfg(**knob)
    with pytest.raises(ValueError, match="sliding-window"):
        ServeEngine(cfg, _params(cfg), batch_slots=2, max_len=32)


def test_preemption_restores_through_recompute_prefill():
    """Forced preemptions re-admit requests through a prefill over prompt
    + emitted tokens (past the window): the streams equal the engine's
    without preemption, and the reference's."""
    cfg = _cfg()
    params = _params(cfg)
    prompts = _prompts(cfg, (6, 11, 4, 17), seed=2)
    budgets = (18, 15, 22, 12)
    _, want = _serve(cfg, params, prompts, budgets)
    chaos = dataclasses.replace(cfg, chaos_preempt_p=0.5, chaos_seed=7)
    eng, got = _serve(chaos, params, prompts, budgets)
    assert eng.stats["preempted"] > 0
    assert [r.generated for r in got] == [r.generated for r in want]
    assert _worst_gap(params, cfg, got) <= TOL


def test_window_and_moe_counters():
    """decode_window_positions_* count per decoded token min(context, W)
    against the whole context; moe_picks_held counts every valid token's
    k picks in every layer (one shard holds all experts here), against
    dispatch rows x experts x layers."""
    cfg = _cfg()
    params = _params(cfg)
    prompt = _prompts(cfg, (6,))[0]
    eng, (r,) = _serve(cfg, params, [prompt], [13], slots=2)
    st = eng.serve_stats()
    W, L, k = cfg.sliding_window, cfg.n_layers, cfg.experts_per_token
    ctx = 6 + np.arange(12)          # cache length before each decode step
    assert st["decode_window_positions_read"] == int(
        np.minimum(ctx + 1, W).sum())
    assert st["decode_window_positions_dense"] == int((ctx + 1).sum())
    assert st["moe_picks_held"] == (6 + 12) * k * L
    # The wave prefills the one request at its own bucket (8), not both
    # slots; each decode step runs both slots.
    rows = (8 + st["chunks"] * eng.chunk_size * 2) * cfg.experts_held * L
    assert st["moe_rows_computed"] == rows
    assert st["host_syncs"] == st["admission_waves"] + st["chunks"]


def test_top_k_sampling_does_not_reroute_moe():
    """ROADMAP D1: the router's experts per token and the sampler's k are
    separate fields, so top-1 sampling serves the greedy streams of a MoE
    model routed over its own 2 experts per token."""
    base = get_config("olmoe-1b-7b", smoke=True)
    params = build_model(base).init(jax.random.PRNGKey(0))
    topk = dataclasses.replace(base, sampling="top_k", top_k=1)
    assert topk.experts_per_token == base.experts_per_token == 2
    prompts = _prompts(base, (5, 9))
    _, want = _serve(base, params, prompts, (8, 8), slots=2)
    _, got = _serve(topk, params, prompts, (8, 8), slots=2)
    assert [r.generated for r in got] == [r.generated for r in want]


def test_blocked_window_attention_matches_naive():
    """The blocked path skips key chunks outside each q block's window
    reach and below every slot's first key, with the naive path's result."""
    q = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (2, 56, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(3), (2, 56, 2, 16))
    kv_len, kv_start = jnp.asarray([50, 56]), jnp.asarray([10, -3])
    want = cm._sdpa_naive(q, k, v, True, 16, kv_len, 16, kv_start)
    got = cm._sdpa_chunked(q, k, v, True, 16, kv_len, chunk=8, q_block=8,
                           window=16, kv_start=kv_start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_yarn_frequencies_at_published_widths():
    """Mellum2's full layers (head 128, theta 5e5, x16 over 8192): the
    ramp runs over dimensions 18-35, so the first 18 frequencies are
    RoPE's, those from 35 on RoPE's / 16; the attention factor is
    0.1 ln 16 + 1; the reference computes the same frequencies."""
    cfg = get_config("mellum2-12b")
    y = cfg.rope_yarn
    got = cm.yarn_inv_freq(128, cfg.rope_theta, y)
    plain = 1.0 / cfg.rope_theta ** (np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(got[:18], plain[:18], rtol=1e-6)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=1e-6)
    assert np.all((got[18:35] < plain[18:35]) & (got[18:35] > plain[18:35] / 16))
    assert y.scale == pytest.approx(0.1 * np.log(16) + 1)
    ref, scale = reference._inv_freq(128, cfg.rope_theta, y)
    np.testing.assert_allclose(got, ref, rtol=1e-6)
    assert scale == pytest.approx(y.scale)
