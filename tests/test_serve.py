"""Serving engine tests: device-resident chunked decode vs full-forward
rollouts — uniform, ragged (mixed prompt lengths), staggered budgets,
continuous re-admission into freed slots, and the paged KV pool
(bit-identity vs the contiguous layout, free-page admission gating)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import pages_per_block
from repro.models import build_model, get_config
from repro.serve.engine import Request, ServeEngine


def _paged(cfg, page_size=8):
    return dataclasses.replace(
        cfg, cache_layout="paged", kv_page_size=page_size
    )


def _greedy_reference(model, params, prompt, n_tokens):
    """Greedy rollout with a full forward pass each step (the oracle)."""
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n_tokens):
        logits, _ = model.forward(params, jnp.asarray([toks], jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


@pytest.mark.parametrize("arch", ["yi-9b", "qwen2.5-32b"])
def test_greedy_decode_matches_full_forward(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (8,), 0, cfg.vocab)
    ).astype(np.int32)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32)
    reqs = [Request(prompt=prompt, max_new_tokens=5),
            Request(prompt=prompt, max_new_tokens=5)]
    eng.run(reqs)
    assert reqs[0].generated == reqs[1].generated  # same prompt, same slots
    assert reqs[0].generated == _greedy_reference(model, params, prompt, 5)


def test_engine_handles_multiple_rounds():
    cfg = get_config("yi-9b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=64)
    prompt = np.arange(4, dtype=np.int32) % cfg.vocab
    reqs = [Request(prompt=prompt, max_new_tokens=3) for _ in range(2)]
    done = eng.run(reqs)
    assert all(r.done for r in done)
    assert all(len(r.generated) == 3 for r in done)


def test_mixed_length_prompts_no_crosstalk():
    """Regression: the seed left-padded prompts without a mask, so padded
    zero tokens were attended during prefill and mixed-length prompts in
    one admission wave cross-contaminated.  Each slot must reproduce its
    own single-request reference exactly."""
    cfg = get_config("yi-9b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (3, 9, 6)]
    eng = ServeEngine(cfg, params, batch_slots=3, max_len=32)
    reqs = [Request(prompt=p, max_new_tokens=5) for p in prompts]
    eng.run(reqs)
    for r in reqs:
        assert r.generated == _greedy_reference(model, params, r.prompt, 5), (
            f"slot {r.slot} diverged from its single-request reference"
        )


@pytest.mark.parametrize("arch", ["yi-9b", "mamba2-1.3b", "zamba2-2.7b"])
def test_ragged_staggered_decode_matches_reference(arch):
    """Mixed prompt lengths AND staggered max_new_tokens: slots park at
    different chunk offsets; every request must match its per-request
    full-forward greedy reference token-for-token."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    rng = np.random.default_rng(1)
    spec = [(4, 7), (8, 3), (5, 5)]        # (prompt_len, max_new_tokens)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                max_new_tokens=m)
        for n, m in spec
    ]
    eng = ServeEngine(cfg, params, batch_slots=3, max_len=32, chunk_size=4)
    eng.run(reqs)
    for r, (n, m) in zip(reqs, spec):
        assert len(r.generated) == m
        assert r.generated == _greedy_reference(model, params, r.prompt, m), (
            f"{arch} slot {r.slot} diverged"
        )


@pytest.mark.parametrize("arch", ["yi-9b", "mamba2-1.3b"])
def test_continuous_admission_reuses_slots(arch):
    """More requests than slots: freed slots re-admit from the queue
    mid-stream, and late requests still match their references (for
    mamba2 this exercises the recurrent-state reset on re-admission)."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(5))
    rng = np.random.default_rng(2)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                max_new_tokens=m)
        for n, m in ((5, 6), (3, 2), (7, 4), (4, 5), (6, 3))
    ]
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32, chunk_size=2)
    eng.run(reqs)
    assert all(r.done for r in reqs)
    assert eng.stats["admission_waves"] >= 3   # slots were recycled
    for r in reqs:
        assert r.generated == _greedy_reference(
            model, params, r.prompt, r.max_new_tokens
        )


def test_chunk_size_invariance():
    """Chunked decode must be bit-identical to per-token decode."""
    cfg = get_config("yi-9b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(6))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (4, 7)]
    outs = []
    for chunk in (1, 8):
        reqs = [Request(prompt=p, max_new_tokens=9) for p in prompts]
        eng = ServeEngine(cfg, params, batch_slots=2, max_len=32,
                          chunk_size=chunk)
        eng.run(reqs)
        outs.append([r.generated for r in reqs])
    assert outs[0] == outs[1]


def test_host_sync_accounting():
    """The point of chunking: at most one decode sync per chunk_size
    decoded tokens (per slot, so usually far fewer)."""
    cfg = get_config("yi-9b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(7))
    chunk = 8
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=64,
                      chunk_size=chunk)
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab
    reqs = [Request(prompt=prompt, max_new_tokens=17) for _ in range(2)]
    eng.run(reqs)
    stats = eng.serve_stats()
    assert stats["decode_tokens"] == 2 * 16
    assert stats["decode_syncs_per_token"] <= 1.0 / chunk
    # TTFT recorded per request
    assert all(r.ttft_s is not None and r.ttft_s > 0 for r in reqs)


@pytest.mark.parametrize(
    "arch", ["yi-9b", "mamba2-1.3b", "zamba2-2.7b", "whisper-small",
             "llama-3.2-vision-90b"]
)
def test_ragged_prefill_matches_per_row_uniform(arch):
    """Model-level ragged contract across all four cache layouts: a ragged
    right-padded prefill + decode step must match per-row uniform runs."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    kwargs = {}
    if cfg.family == "vlm":
        kwargs["vis"] = jax.random.normal(
            jax.random.PRNGKey(3), (2, cfg.n_vis_tokens, cfg.d_model),
            jnp.float32,
        )
    if cfg.family == "encdec":
        kwargs["frames"] = jax.random.normal(
            jax.random.PRNGKey(4), (2, cfg.enc_seq, cfg.d_model), jnp.float32
        )
    lens = [5, 8]
    toks = np.array(
        jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab),
        np.int32,
    )
    for b, n in enumerate(lens):
        toks[b, n:] = 0   # right-pad garbage that must never leak in
    cache = model.init_cache(params, batch=2, max_len=16, **kwargs)
    lg, cache = model.prefill(
        params, cache, jnp.asarray(toks),
        seg_lens=jnp.asarray(lens, jnp.int32),
    )
    nxt = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
    lg2, cache = model.decode_step(
        params, cache, nxt[:, None], seg_lens=jnp.asarray([1, 1], jnp.int32)
    )
    for b, n in enumerate(lens):
        kw1 = {k: v[b:b + 1] for k, v in kwargs.items()}
        c1 = model.init_cache(params, batch=1, max_len=16, **kw1)
        l1, c1 = model.prefill(params, c1, jnp.asarray(toks[b:b + 1, :n]))
        np.testing.assert_allclose(
            np.asarray(lg[b, -1], np.float32),
            np.asarray(l1[0, -1], np.float32),
            rtol=2e-2, atol=2e-2, err_msg=f"{arch} ragged prefill slot {b}",
        )
        assert int(jnp.argmax(l1[0, -1])) == int(nxt[b])
        l2, _ = model.decode_step(params, c1, nxt[b][None, None])
        np.testing.assert_allclose(
            np.asarray(lg2[b, -1], np.float32),
            np.asarray(l2[0, -1], np.float32),
            rtol=2e-2, atol=2e-2, err_msg=f"{arch} ragged decode slot {b}",
        )


def test_parked_slot_state_untouched():
    """seg_lens == 0 must leave a slot's cache state bit-identical (how
    finished slots ride inside a chunk without corruption)."""
    cfg = get_config("mamba2-1.3b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(8))
    toks = jax.random.randint(jax.random.PRNGKey(9), (2, 6), 0, cfg.vocab)
    cache = model.init_cache(params, batch=2, max_len=16)
    _, cache = model.prefill(params, cache, toks)
    step_tok = jnp.zeros((2, 1), jnp.int32)
    _, cache2 = model.decode_step(
        params, cache, step_tok, seg_lens=jnp.asarray([0, 1], jnp.int32)
    )
    # Slot 0 parked: every leaf's row 0 unchanged.
    assert int(cache2["lengths"][0]) == int(cache["lengths"][0])
    assert int(cache2["lengths"][1]) == int(cache["lengths"][1]) + 1
    np.testing.assert_array_equal(
        np.asarray(cache["ssm"][:, 0]), np.asarray(cache2["ssm"][:, 0])
    )
    np.testing.assert_array_equal(
        np.asarray(cache["conv"][:, 0]), np.asarray(cache2["conv"][:, 0])
    )


def test_submit_rejects_zero_token_budget():
    """Regression: admission always emits the prefill-sampled first token,
    so max_new_tokens == 0 used to over-generate by one.  Reject at
    submit instead."""
    cfg = get_config("yi-9b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32)
    prompt = np.arange(4, dtype=np.int32) % cfg.vocab
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([Request(prompt=prompt, max_new_tokens=0)])
    # The engine stays usable: a valid request still serves.
    r = Request(prompt=prompt, max_new_tokens=2)
    eng.run([r])
    assert r.done and len(r.generated) == 2


def test_queue_wait_separated_from_ttft():
    """Regression: ttft_s used to be stamped submit→first-token, folding
    queue wait into "TTFT".  Now queue_wait_s is submit→admission and
    ttft_s is admission→first-token, per request."""
    cfg = get_config("yi-9b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(1))
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=32, chunk_size=2)
    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab, size=4).astype(np.int32),
                max_new_tokens=5)
        for _ in range(3)
    ]
    eng.run(reqs)
    for r in reqs:
        assert r.queue_wait_s is not None and r.queue_wait_s >= 0
        assert r.ttft_s is not None and r.ttft_s > 0
    # With one slot, later requests wait through earlier decode chunks:
    # their queue wait dominates the first request's.
    assert reqs[-1].queue_wait_s > reqs[0].queue_wait_s


def test_reset_recurrent_batch_axis_guard():
    """Regression: reset_recurrent silently assumed batch on axis 1 for
    every state leaf; a layout with batch elsewhere must fail loudly (and
    work when the axis is passed explicitly)."""
    from repro.models.common import reset_recurrent

    mask = jnp.asarray([True, False])
    cache = {
        "lengths": jnp.asarray([3, 4], jnp.int32),
        "ssm": jnp.ones((3, 2, 4), jnp.float32),     # (L, b, ...) — fine
        "conv": jnp.ones((2, 5, 7), jnp.float32),    # batch on axis 0!
    }
    with pytest.raises(ValueError, match="conv"):
        reset_recurrent(cache, mask)
    out = reset_recurrent(cache, mask, state_keys=("ssm", ("conv", 0)))
    np.testing.assert_array_equal(np.asarray(out["ssm"][:, 0]), 0.0)
    np.testing.assert_array_equal(np.asarray(out["ssm"][:, 1]), 1.0)
    np.testing.assert_array_equal(np.asarray(out["conv"][0]), 0.0)
    np.testing.assert_array_equal(np.asarray(out["conv"][1]), 1.0)


# ---------------------------------------------------------------------------
# Paged KV pool (DESIGN.md §5.2)
# ---------------------------------------------------------------------------

def test_paged_model_logits_bit_identical():
    """Model-level: with a hand-built page table, paged prefill + decode
    logits must equal the contiguous layout BIT-for-bit (same masked
    online-softmax over an identically-shaped gathered view)."""
    cfg = get_config("yi-9b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab)
    seg = jnp.asarray([4, 6], jnp.int32)

    cache_c = model.init_cache(params, batch=2, max_len=16)
    lc, cache_c = model.prefill(params, cache_c, toks, seg_lens=seg)
    nxt = jnp.argmax(lc[:, -1], -1).astype(jnp.int32)
    lc2, _ = model.decode_step(
        params, cache_c, nxt[:, None], seg_lens=jnp.asarray([1, 1], jnp.int32)
    )

    pmodel = build_model(_paged(cfg))
    cache_p = pmodel.init_cache(params, batch=2, max_len=16)
    # max_len=16, page_size=8 -> 2 logical pages per slot; map them to
    # scattered physical pages to exercise the translation.
    cache_p["pages"] = jnp.asarray([[3, 0], [1, 2]], jnp.int32)
    lp, cache_p = pmodel.prefill(params, cache_p, toks, seg_lens=seg)
    lp2, _ = pmodel.decode_step(
        params, cache_p, nxt[:, None], seg_lens=jnp.asarray([1, 1], jnp.int32)
    )
    np.testing.assert_array_equal(np.asarray(lc), np.asarray(lp))
    np.testing.assert_array_equal(np.asarray(lc2), np.asarray(lp2))


@pytest.mark.parametrize(
    "arch", ["yi-9b", "zamba2-2.7b", "whisper-small", "llama-3.2-vision-90b"]
)
def test_paged_engine_bit_identical_to_contiguous(arch):
    """Serve-level: the same mixed-length/staggered-budget workload through
    a paged engine with a POOLED page budget (smaller than slots x max_len)
    must emit exactly the contiguous engine's tokens, across every cache
    family that has a KV cache."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    extras = {}
    if cfg.family == "vlm":
        extras["vis"] = jax.random.normal(
            jax.random.PRNGKey(3), (2, cfg.n_vis_tokens, cfg.d_model),
            jnp.float32,
        )
    if cfg.family == "encdec":
        extras["frames"] = jax.random.normal(
            jax.random.PRNGKey(4), (2, cfg.enc_seq, cfg.d_model), jnp.float32
        )

    def requests():
        rng = np.random.default_rng(1)
        return [
            Request(prompt=rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                    max_new_tokens=m)
            for n, m in ((4, 7), (8, 3), (5, 5), (3, 6))
        ]

    ref = requests()
    ServeEngine(cfg, params, batch_slots=2, max_len=32, chunk_size=4,
                extras=extras).run(ref)
    got = requests()
    # 5 pages x 8 tokens = 40 pooled positions < 2 slots x 32 = 64.
    eng = ServeEngine(_paged(cfg), params, batch_slots=2, max_len=32,
                      chunk_size=4, extras=extras, n_pages=5)
    eng.run(got)
    for a, b in zip(ref, got):
        assert a.generated == b.generated, f"{arch}: paged != contiguous"
    assert sorted(eng.free_pages) == list(range(5))   # all pages returned


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "zamba2-2.7b"])
def test_decode_kernel_paged_bit_identical_to_gather(arch):
    """The paged Pallas decode kernel (in-kernel page-table dereference,
    no gather copy) must emit exactly the tokens of the gather-path
    reference (``decode_kernel="pallas_gather"``: gather_pages + the same
    dense split-KV kernel) on a pooled paged engine — the clamp-to-page-0
    -then-mask contract is the reference semantics."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def run(decode_kernel):
        rng = np.random.default_rng(2)
        reqs = [
            Request(prompt=rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                    max_new_tokens=m)
            for n, m in ((4, 8), (9, 4), (5, 6), (3, 7))
        ]
        eng = ServeEngine(
            dataclasses.replace(_paged(cfg), decode_kernel=decode_kernel),
            params, batch_slots=2, max_len=32, chunk_size=4, n_pages=5,
        )
        eng.run(reqs)
        assert sorted(eng.free_pages) == list(range(5))
        return [r.generated for r in reqs], eng

    gather, _ = run("pallas_gather")
    paged, eng = run("pallas_paged")
    assert paged == gather, f"{arch}: paged kernel != gather path"
    rep = eng.policy_report()["decode_attention"]
    assert rep["kernel"] == "pallas_paged"
    assert rep["planned_splits"] >= 1
    # Both paths fold the same compute block of several pages.
    page_bytes = (eng.page_size * eng.cfg.n_kv_heads * eng.cfg.head_dim_
                  * jnp.dtype(eng.cfg.dtype).itemsize)
    ppb = pages_per_block(page_bytes, eng.pages_per_slot)
    assert ppb > 1
    assert rep["kernel_bkv"] == ppb * eng.page_size


def test_decode_kernel_splits_baked_from_plan(monkeypatch):
    """The report's split count and block are the decode kernels' own rule
    for the engine's cache — the paged pool's pages or the contiguous
    ring's positions against the chip's cores — not a plan of the
    engine's."""
    from repro.kernels.decode_attention import ops

    monkeypatch.setattr(ops, "split_cores", lambda: 4)
    cfg = get_config("qwen2.5-32b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    paged = ServeEngine(
        dataclasses.replace(_paged(cfg), decode_kernel="pallas_paged"),
        params, batch_slots=2, max_len=32,
    )
    page_bytes = (paged.page_size * cfg.n_kv_heads * cfg.head_dim_
                  * jnp.dtype(cfg.dtype).itemsize)
    ppb, splits = ops.paged_plan(page_bytes, paged.pages_per_slot)
    assert paged.policy_report()["decode_attention"] == {
        "kernel": "pallas_paged", "planned_splits": splits,
        "kernel_bkv": ppb * paged.page_size,
    }
    assert splits == ops.split_count(32, ppb * paged.page_size)
    ring = ServeEngine(dataclasses.replace(cfg, decode_kernel="pallas_gather"),
                       params, batch_slots=2, max_len=1536)
    assert ring.policy_report()["decode_attention"] == {
        "kernel": "pallas_gather", "planned_splits": 3, "kernel_bkv": 512,
    }


@pytest.mark.parametrize("layout,backend,want", [
    ("paged", "cpu", "xla"), ("contiguous", "cpu", "xla"),
    ("paged", "tpu", "pallas_paged"), ("contiguous", "tpu", "xla"),
])
def test_decode_kernel_resolved_at_construction(layout, backend, want,
                                                monkeypatch):
    """The default ("auto") decode path is resolved once from what the
    engine observes: the paged kernel on a paged cache on a TPU, XLA on a
    contiguous cache or off the chip; the traced config and the policy
    report carry the resolved path."""
    cfg = get_config("qwen2.5-32b", smoke=True)
    assert cfg.decode_kernel == "auto"
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    cfg = dataclasses.replace(cfg, cache_layout=layout, kv_page_size=8)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: backend)
        eng = ServeEngine(cfg, params, batch_slots=2, max_len=32)
    assert eng.cfg.decode_kernel == want
    assert eng.policy_report()["decode_attention"]["kernel"] == want


@pytest.mark.parametrize("explicit", ["xla", "pallas_gather", "pallas_paged"])
def test_decode_kernel_explicit_wins(explicit, monkeypatch):
    cfg = dataclasses.replace(_paged(get_config("qwen2.5-32b", smoke=True)),
                              decode_kernel=explicit)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        eng = ServeEngine(cfg, params, batch_slots=2, max_len=32)
    assert eng.cfg.decode_kernel == explicit
    assert eng.policy_report()["decode_attention"]["kernel"] == explicit


def test_decode_pages_counters():
    """Per decode chunk: the paged kernel reads the decoding slots' mapped
    pages, the gather path every slot's whole table, each x chunk_size.
    Slots 2 x 4 pages of 8, chunk 4: A (9 + 8 tokens, 2 pages) and B (3 +
    4, 1 page) decode one chunk together, then A alone."""
    cfg = _paged(get_config("qwen2.5-32b", smoke=True))
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32, chunk_size=4)
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                    max_new_tokens=m) for n, m in ((9, 8), (3, 4))]
    eng.run(reqs)
    stats = eng.serve_stats()
    assert stats["chunks"] == 2
    assert stats["decode_pages_read"] == 4 * (2 + 1) + 4 * 2
    assert stats["decode_pages_dense"] == 2 * 4 * 2 * 4
    assert stats["decode_pages_read"] <= stats["decode_pages_dense"]


def test_paged_pool_oversubscription_mixed_lengths():
    """The acceptance workload: a mixed long/short request set runs in a
    page pool HALF the contiguous reservation (2x effective capacity) and
    every request still matches its full-forward greedy reference."""
    cfg = get_config("yi-9b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    rng = np.random.default_rng(3)
    slots, max_len, page = 3, 32, 8
    # Contiguous would reserve 3 x 32 = 96 positions; the pool holds 48.
    n_pages = 6
    assert n_pages * page * 2 == slots * max_len
    spec = [(20, 12), (4, 5), (6, 3), (3, 6), (5, 4)]   # 1 long + shorts
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                max_new_tokens=m)
        for n, m in spec
    ]
    eng = ServeEngine(_paged(cfg), params, batch_slots=slots, max_len=max_len,
                      chunk_size=4, n_pages=n_pages)
    eng.run(reqs)
    assert all(r.done for r in reqs)
    for r in reqs:
        assert r.generated == _greedy_reference(
            model, params, r.prompt, r.max_new_tokens
        ), f"slot {r.slot} diverged under page-pool oversubscription"
    assert sorted(eng.free_pages) == list(range(n_pages))


def test_paged_admission_gates_on_free_pages():
    """A pool that fits only one request at a time must serialize admission
    (FIFO head-of-line) instead of admitting into a free slot without
    pages — and still complete everything correctly."""
    cfg = get_config("yi-9b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    rng = np.random.default_rng(5)
    # Each request needs 2 pages (need 9..16 tokens); pool has 2 -> one
    # in flight at a time even though 2 slots are free.
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab, size=6).astype(np.int32),
                max_new_tokens=6)
        for _ in range(3)
    ]
    eng = ServeEngine(_paged(cfg), params, batch_slots=2, max_len=16,
                      chunk_size=2, n_pages=2)
    eng.run(reqs)
    assert all(r.done for r in reqs)
    assert eng.stats["admission_waves"] >= 3           # serialized
    for r in reqs:
        assert r.generated == _greedy_reference(model, params, r.prompt, 6)


def test_paged_falls_back_for_kv_free_families():
    """A paged config on a cache family with no KV (mamba2) must fall back
    to contiguous bookkeeping — no phantom page pool gating admission —
    and still serve correctly."""
    cfg = get_config("mamba2-1.3b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    rng = np.random.default_rng(6)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab, size=5).astype(np.int32),
                max_new_tokens=4)
        for _ in range(3)
    ]
    # n_pages=1 would gate admission to one request at a time if the
    # phantom pool were honored.
    eng = ServeEngine(_paged(cfg), params, batch_slots=2, max_len=16,
                      chunk_size=2, n_pages=1)
    assert not eng.paged
    assert eng.policy_report()["cache_layout"] == "contiguous"
    eng.run(reqs)
    for r in reqs:
        assert r.generated == _greedy_reference(model, params, r.prompt, 4)


def test_paged_policy_report_sees_pooled_bytes():
    """The report gives the pool's real capacity beside the contiguous
    worst case it replaces."""
    cfg = get_config("yi-9b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(6))
    pooled = ServeEngine(_paged(cfg), params, batch_slots=4, max_len=32,
                         n_pages=8)   # 64 positions vs 128 contiguous
    rp = pooled.policy_report()
    assert rp["paged_kv"]["pool_positions"] == 64
    assert rp["paged_kv"]["contiguous_positions"] == 128


_NO_PLANNER = """
import dataclasses, sys
import jax, numpy as np
from repro.models import build_model, get_config
from repro.serve.engine import Request, ServeEngine

cfg = dataclasses.replace(get_config(sys.argv[1], smoke=True),
                          cache_layout="paged", kv_page_size=8)
if cfg.family == "dense":
    cfg = dataclasses.replace(cfg, prefix_sharing=True, adaptive=True,
                              warm_pages=2)
extras = {}
if cfg.family == "encdec":
    extras["frames"] = np.zeros((2, cfg.enc_seq, cfg.d_model), np.float32)
params = build_model(cfg).init(jax.random.PRNGKey(0))
eng = ServeEngine(cfg, params, batch_slots=2, max_len=32, chunk_size=4,
                  extras=extras)
req = Request(prompt=np.arange(5, dtype=np.int32), max_new_tokens=3)
eng.run([req])
assert len(req.generated) == 3 and eng.policy_report()["adaptive"]["enabled"] \
    == (cfg.family == "dense")
print(sorted(m for m in sys.modules
             if m == "repro.core" or m.startswith("repro.core.")))
"""


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "zamba2-2.7b",
                                  "whisper-small"])
def test_serving_path_loads_no_planner(arch):
    """Serving a request loads none of the paper's cache-policy planner
    (``repro.core``): the kernels plan from shapes and the chip, the
    adaptive tier argmins its own lattice.  In a fresh process, since
    other tests import ``repro.core`` into this one."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", _NO_PLANNER, arch], capture_output=True,
        text=True, timeout=300, cwd=root,
        env=dict(os.environ, PYTHONPATH=os.path.join(root, "src")),
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.splitlines()[-1] == "[]"


# ---------------------------------------------------------------------------
# Prefix sharing (DESIGN.md §5.4): attaching a request to resident prefix
# pages must be invisible in the emitted stream — bit-identical to the
# unshared engine on every cell of {prefix on/off} x {contiguous, paged} x
# {qwen, zamba2, whisper} — and the refcount lifecycle must never free a
# page a sharer still references.
# ---------------------------------------------------------------------------

PREFIX_ARCHS = ["qwen2.5-32b", "zamba2-2.7b", "whisper-small"]
_PREFIX_SYS = 17      # system-prompt tokens: 2 full pages of 8 + 1 spilled


def _prefix_requests(cfg, sys_len=_PREFIX_SYS, seed=4,
                     spec=((3, 5), (5, 4), (2, 6), (4, 3))):
    """Many slots, one system prompt: every request is sys + own tail."""
    rng = np.random.default_rng(seed)
    sys_p = rng.integers(0, cfg.vocab, size=sys_len).astype(np.int32)
    return [
        Request(prompt=np.concatenate(
            [sys_p, rng.integers(0, cfg.vocab, size=n).astype(np.int32)]),
            max_new_tokens=m)
        for n, m in spec
    ]


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("arch", PREFIX_ARCHS)
def test_prefix_sharing_bit_identical_matrix(arch, layout):
    """Sharing genuinely engages only for qwen+paged (pure-KV decoder
    family over the page pool); every other cell verifies the graceful
    fallback — requested but disabled — leaves the stream untouched."""
    cfg = get_config(arch, smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    extras = _spec_extras(cfg, 4)

    def run(c):
        reqs = _prefix_requests(cfg)
        eng = ServeEngine(c, params, batch_slots=4, max_len=32,
                          chunk_size=4, extras=extras)
        eng.run(reqs)
        return eng, reqs

    base = cfg if layout == "contiguous" else _paged(cfg)
    _, ref = run(base)
    eng, got = run(dataclasses.replace(base, prefix_sharing=True))
    expect = layout == "paged" and arch == "qwen2.5-32b"
    assert eng.prefix_sharing == expect
    rep = eng.policy_report()["prefix_sharing"]
    assert rep["requested"] is True and rep["enabled"] is expect
    if expect:
        # All four ride one admission wave: the first request registers,
        # the other three attach to its (not-yet-prefilled) pages — the
        # same-wave case, where the suffix rows read K/V the owner's rows
        # write inside the same dispatch.
        assert eng.stats["prefix_hits"] == 3
        assert eng.stats["prefix_tokens_shared"] == 3 * 16
        assert all(r.prefix_tokens == (0 if i == 0 else 16)
                   for i, r in enumerate(got))
    for a, b in zip(ref, got):
        assert len(b.generated) == b.max_new_tokens
        assert a.generated == b.generated, (
            f"{arch}/{layout}: prefix sharing changed the stream"
        )


def test_prefix_cow_divergence():
    """COW semantics: (B) a prompt that ends exactly at a shared-page
    boundary re-materializes its last page privately (the seeding logits
    are never assumed resident), and (C) a prompt diverging mid-page gets
    a private divergent page — the shared page is never written, so every
    stream matches its own full-forward reference."""
    cfg = get_config("qwen2.5-32b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    base = rng.integers(0, cfg.vocab, size=16).astype(np.int32)  # 2 pages
    A = Request(prompt=np.concatenate(
        [base, rng.integers(0, cfg.vocab, size=4).astype(np.int32)]),
        max_new_tokens=4)
    B = Request(prompt=base.copy(), max_new_tokens=8)
    C = Request(prompt=np.concatenate(
        [base[:12], rng.integers(0, cfg.vocab, size=6).astype(np.int32)]),
        max_new_tokens=6)
    eng = ServeEngine(
        dataclasses.replace(_paged(cfg), prefix_sharing=True), params,
        batch_slots=3, max_len=32, chunk_size=2,
    )
    eng.run([A, B, C])
    assert A.prefix_tokens == 0          # first in: registers, shares nothing
    assert B.prefix_tokens == 8          # capped below its 2-page prompt
    assert C.prefix_tokens == 8          # page 1 diverges -> only page 0
    for r, name in ((A, "A"), (B, "B"), (C, "C")):
        assert r.generated == _greedy_reference(
            model, params, r.prompt, r.max_new_tokens
        ), f"{name} diverged under COW"
    assert sorted(eng.free_pages) == list(range(eng.n_pages))
    assert len(eng.prefix) == 0


def test_prefix_refcount_at_finish():
    """Regression: the prefix owner finishing first must not free pages a
    sharer still references — they free (and their trie nodes evict) only
    when the LAST sharer finishes."""
    cfg = get_config("qwen2.5-32b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    sys_p = rng.integers(0, cfg.vocab, size=17).astype(np.int32)
    owner = Request(prompt=np.concatenate(
        [sys_p, rng.integers(0, cfg.vocab, size=3).astype(np.int32)]),
        max_new_tokens=1)            # finishes at the admission wave itself
    sharers = [
        Request(prompt=np.concatenate(
            [sys_p, rng.integers(0, cfg.vocab, size=n).astype(np.int32)]),
            max_new_tokens=6)
        for n in (4, 2)
    ]
    eng = ServeEngine(
        dataclasses.replace(_paged(cfg), prefix_sharing=True), params,
        batch_slots=3, max_len=32, chunk_size=2,
    )
    eng.submit([owner] + sharers)
    eng._admit_wave()
    assert owner.done and not any(s.done for s in sharers)
    # The two shared pages survive the owner's release at refcount 2.
    assert len(eng.prefix) == 2
    shared_pages = eng.prefix.lookup(sys_p[:16])
    assert [eng.allocator.ref_count(p) for p in shared_pages] == [2, 2]
    eng.drain()
    for r in sharers:
        assert r.generated == _greedy_reference(
            model, params, r.prompt, r.max_new_tokens
        )
    assert sorted(eng.free_pages) == list(range(eng.n_pages))
    assert len(eng.prefix) == 0


def test_prefix_sharing_composes_with_spec():
    """Prefix sharing under speculative decode: outputs stay identical
    AND acceptance is preserved — the n-gram history seeds from the full
    prompt (not just the prefilled suffix), so an attached slot drafts
    exactly what the unshared engine drafts."""
    cfg = get_config("qwen2.5-32b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(6)
    # A repetitive system prompt the proposer can mine from round one.
    sys_p = np.tile(rng.integers(0, cfg.vocab, size=4), 5)[:17].astype(
        np.int32
    )
    def run(c):
        reqs = [
            Request(prompt=np.concatenate(
                [sys_p, rng2.integers(0, cfg.vocab, size=n).astype(np.int32)]),
                max_new_tokens=8)
            for rng2, n in ((np.random.default_rng(7 + i), 3 + i)
                            for i in range(4))
        ]
        eng = ServeEngine(c, params, batch_slots=4, max_len=32, chunk_size=8)
        eng.run(reqs)
        return eng, reqs

    spec_paged = dataclasses.replace(_paged(cfg), spec_k=3, spec_ngram=2)
    eng_u, ref = run(spec_paged)
    eng_s, got = run(dataclasses.replace(spec_paged, prefix_sharing=True))
    assert eng_s.prefix_sharing and eng_s.stats["prefix_hits"] == 3
    for a, b in zip(ref, got):
        assert a.generated == b.generated
    # Same full-prompt history -> same drafts -> identical acceptance.
    for k in ("draft_proposed", "draft_accepted", "spec_rounds"):
        assert eng_s.stats[k] == eng_u.stats[k], k


def test_prefix_sharing_raises_effective_capacity():
    """The point of the feature: a pool too small to hold the workload
    unshared admits EVERY slot in one wave once the system prompt is
    shared — and still emits the unshared engine's exact streams."""
    cfg = get_config("qwen2.5-32b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    spec = ((3, 5), (5, 4), (4, 6), (3, 3))   # 4 pages worst-case each

    def run(c):
        reqs = _prefix_requests(cfg, spec=spec)
        eng = ServeEngine(c, params, batch_slots=4, max_len=32,
                          chunk_size=4, n_pages=10)
        eng.run(reqs)
        return eng, reqs

    eng_u, ref = run(_paged(cfg))
    eng_s, got = run(dataclasses.replace(_paged(cfg), prefix_sharing=True))
    # Unshared: the four requests need 3+4+4+3 = 14 pages > 10 pooled ->
    # admission serializes behind page frees.
    assert eng_u.stats["admission_waves"] >= 2
    assert eng_u.stats["peak_pages_held"] <= 10
    # Shared: 3 (owner) + 2+2+1 suffix-only pages = 8 <= 10 -> one wave.
    assert eng_s.stats["admission_waves"] == 1
    assert eng_s.stats["peak_pages_held"] == 8
    for a, b in zip(ref, got):
        assert a.generated == b.generated
    assert eng_s.serve_stats()["prefix_hit_rate"] == 0.75


# ---------------------------------------------------------------------------
# Speculative decode (DESIGN.md §5.3): draft/verify/rollback must be
# output-identical to plain chunked decode for every cache family and both
# KV layouts — the headline invariant of the spec path.
# ---------------------------------------------------------------------------

SPEC_WORKLOAD = ((4, 9), (8, 3), (5, 7), (3, 8))   # (prompt_len, max_new)


def _spec_extras(cfg, slots):
    """Slot extras for the spec matrix.  Encoder frames / vision tokens are
    PER-SLOT stub constants (requests don't carry their own audio/image),
    so a request's output depends on which slot admits it.  Spec and plain
    engines reach different admission schedules (different chunk
    granularity), so the identity matrix tiles ONE row across slots — the
    per-request source context is then independent of slot assignment."""
    if cfg.family == "encdec":
        row = np.asarray(jax.random.normal(
            jax.random.PRNGKey(4), (1, cfg.enc_seq, cfg.d_model), jnp.float32
        ))
        return {"frames": np.tile(row, (slots, 1, 1))}
    if cfg.family == "vlm":
        row = np.asarray(jax.random.normal(
            jax.random.PRNGKey(3), (1, cfg.n_vis_tokens, cfg.d_model),
            jnp.float32,
        ))
        return {"vis": np.tile(row, (slots, 1, 1))}
    return {}


def _spec_requests(cfg, workload=SPEC_WORKLOAD, seed=1):
    rng = np.random.default_rng(seed)
    return [
        Request(prompt=rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                max_new_tokens=m)
        for n, m in workload
    ]


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize(
    "arch", ["qwen2.5-32b", "mamba2-1.3b", "zamba2-2.7b", "whisper-small",
             "llama-3.2-vision-90b"]
)
def test_spec_decode_bit_identical_matrix(arch, layout):
    """{spec on/off} x {contiguous, paged} x {all four cache families}:
    greedy outputs must all be equal (and exactly max_new_tokens long).
    Exercises both rollback schemes: cursor rewind (qwen/whisper) and
    recurrent replay (mamba2/zamba2)."""
    cfg = get_config(arch, smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    extras = _spec_extras(cfg, 2)

    def run(c, **kw):
        reqs = _spec_requests(cfg)
        ServeEngine(c, params, batch_slots=2, max_len=32, chunk_size=8,
                    extras=extras, **kw).run(reqs)
        return reqs

    base = dataclasses.replace(cfg) if layout == "contiguous" else _paged(cfg)
    kw = {"n_pages": 5} if layout == "paged" else {}
    ref = run(base, **kw)
    spec_cfg = dataclasses.replace(base, spec_k=3, spec_ngram=2)
    eng_reqs = run(spec_cfg, **kw)
    for a, b in zip(ref, eng_reqs):
        assert len(b.generated) == a.max_new_tokens
        assert a.generated == b.generated, (
            f"{arch}/{layout}: speculative != plain greedy decode"
        )


def test_spec_k_and_chunk_size_invariance():
    """The emitted stream must not depend on how many tokens are drafted
    per round or how many rounds ride in one dispatch."""
    cfg = get_config("qwen2.5-32b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(6))
    outs = []
    for spec_k, chunk in ((1, 4), (3, 8), (6, 28), (3, 56)):
        reqs = _spec_requests(cfg, seed=3)
        ServeEngine(
            dataclasses.replace(cfg, spec_k=spec_k, spec_ngram=2),
            params, batch_slots=2, max_len=32, chunk_size=chunk,
        ).run(reqs)
        outs.append([r.generated for r in reqs])
    assert all(o == outs[0] for o in outs[1:])


def test_spec_continuous_readmission_resets_history():
    """More requests than slots under spec: freed slots re-admit mid-
    stream, and the re-admitted slot's draft history must not leak the
    previous occupant's tokens (outputs still match non-spec)."""
    cfg = get_config("qwen2.5-32b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(5))
    workload = ((5, 6), (3, 2), (7, 4), (4, 5), (6, 3))

    def run(c):
        reqs = _spec_requests(cfg, workload=workload, seed=2)
        eng = ServeEngine(c, params, batch_slots=2, max_len=32, chunk_size=8)
        eng.run(reqs)
        return eng, reqs

    _, ref = run(cfg)
    eng, got = run(dataclasses.replace(cfg, spec_k=3, spec_ngram=2))
    assert eng.stats["admission_waves"] >= 3      # slots were recycled
    for a, b in zip(ref, got):
        assert a.generated == b.generated


def test_spec_acceptance_accounting():
    """A request resumed deep inside its own repetitive stream must see
    nonzero draft acceptance, and serve_stats must expose the rate."""
    cfg = get_config("qwen2.5-32b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # The constant-7 prompt drives the smoke model into a fixed point
    # (greedy emits 7 forever), so the resumed window is fully
    # n-gram-predictable.
    probe = Request(
        prompt=np.full(6, 7, np.int32), max_new_tokens=24
    )
    ServeEngine(cfg, params, batch_slots=1, max_len=64).run([probe])
    # Resume 16 tokens in: the continuation equals the rest of the probe
    # stream (greedy determinism), which the n-gram proposer can mine.
    resume = Request(
        prompt=np.concatenate(
            [probe.prompt, np.asarray(probe.generated[:16], np.int32)]
        ),
        max_new_tokens=8,
    )
    eng = ServeEngine(
        dataclasses.replace(cfg, spec_k=3, spec_ngram=2), params,
        batch_slots=1, max_len=64, chunk_size=8,
    )
    eng.run([resume])
    assert resume.generated == probe.generated[16:24]
    stats = eng.serve_stats()
    assert stats["draft_proposed"] > 0
    assert 0.0 <= stats["spec_acceptance_rate"] <= 1.0
    # Spec must emit strictly more than one token per verify round here
    # (the stream is repetitive), i.e. fewer dispatched rounds than tokens.
    assert stats["spec_tokens_per_round"] > 1.0


# ---------------------------------------------------------------------------
# Seeded sampling (DESIGN.md §5.3): keys fold from (seed, token index),
# never from the slot — streams survive submission re-ordering.
# ---------------------------------------------------------------------------

def _seeded_requests(cfg, order, prompts):
    return [Request(prompt=prompts[i], max_new_tokens=6, seed=100 + i)
            for i in order]


def test_seeded_sampling_order_independent():
    """Regression: temperature sampling used to be nondeterministic across
    runs and slot assignments.  With per-request seeds, re-ordered
    submissions must yield identical tokens per request."""
    cfg = dataclasses.replace(
        get_config("qwen2.5-32b", smoke=True),
        sampling="temperature", temperature=0.8,
    )
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (4, 6, 5, 7)]

    def run(order):
        reqs = _seeded_requests(cfg, order, prompts)
        ServeEngine(cfg, params, batch_slots=2, max_len=32,
                    chunk_size=4).run(reqs)
        return {r.seed: r.generated for r in reqs}

    first = run([0, 1, 2, 3])
    shuffled = run([3, 1, 0, 2])
    assert first == shuffled, "streams depend on slot assignment order"
    # The seeds genuinely differentiate streams (not all-greedy collapse).
    assert len({tuple(v) for v in first.values()}) > 1


def test_seeded_sampling_spec_identity():
    """Speculative verification replays the exact (seed, token-index)
    sampler decision, so spec decode is output-identical under stochastic
    sampling too — not just greedy."""
    cfg = dataclasses.replace(
        get_config("qwen2.5-32b", smoke=True),
        sampling="temperature", temperature=0.8,
    )
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (4, 6, 5, 7)]

    def run(c):
        reqs = _seeded_requests(cfg, [0, 1, 2, 3], prompts)
        ServeEngine(c, params, batch_slots=2, max_len=32,
                    chunk_size=8).run(reqs)
        return {r.seed: r.generated for r in reqs}

    assert run(cfg) == run(
        dataclasses.replace(cfg, spec_k=3, spec_ngram=2)
    )


def test_large_and_negative_seeds_fold_safely():
    """Regression: seeds from 64-bit hashes (or negatives) must not crash
    the admission wave's int32 cast — they fold deterministically."""
    cfg = dataclasses.replace(
        get_config("qwen2.5-32b", smoke=True),
        sampling="temperature", temperature=0.9,
    )
    params = build_model(cfg).init(jax.random.PRNGKey(2))
    prompt = np.arange(4, dtype=np.int32) % cfg.vocab

    def run(seed):
        r = Request(prompt=prompt, max_new_tokens=5, seed=seed)
        ServeEngine(cfg, params, batch_slots=1, max_len=32).run([r])
        return r.generated

    big = run(2 ** 33 + 5)
    assert big == run(2 ** 33 + 5)          # reproducible
    assert big == run((2 ** 33 + 5) % 2 ** 31)  # folds, not truncates
    assert run(-3) == run(-3)


def test_default_seed_reproducible():
    """Requests without an explicit seed share the default stream: two
    identical submissions reproduce bit-identical outputs."""
    cfg = dataclasses.replace(
        get_config("qwen2.5-32b", smoke=True), sampling="top_k", top_k=4,
        temperature=0.9,
    )
    params = build_model(cfg).init(jax.random.PRNGKey(1))
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab

    def run():
        r = Request(prompt=prompt, max_new_tokens=7)
        ServeEngine(cfg, params, batch_slots=1, max_len=32).run([r])
        return r.generated

    assert run() == run()


# ---------------------------------------------------------------------------
# Adaptive cache policy (DESIGN.md §5.7): the counter-driven controller
# may move PAGES (warm retention, cost-aware victims, per-class
# replanning) but never TOKENS — every cell of the matrix must be
# bit-identical to the static engine, including under chaos.
# ---------------------------------------------------------------------------


def _adaptive(cfg, warm=3, every=2):
    return dataclasses.replace(cfg, adaptive=True, warm_pages=warm,
                               adaptive_replan_every=every)


def _assert_warm_conserved(eng):
    """Zero leaks with the warm tier live: free + warm + quarantined is
    the whole pool once nothing is resident."""
    free = sorted(eng.allocator.free_pages)
    warm = sorted(eng.allocator.warm_pages)
    quar = sorted(eng.allocator.quarantined_pages)
    assert sorted(free + warm + quar) == list(range(eng.n_pages)), (
        free, warm, quar
    )
    eng.check_invariants()


@pytest.mark.parametrize("sharing", [False, True])
@pytest.mark.parametrize("arch", PREFIX_ARCHS)
def test_adaptive_bit_identity_matrix(arch, sharing):
    """Adaptive on vs off across {qwen, zamba2, whisper} x {sharing
    on, off}, plus a chaos leg per cell (seeded alloc refusals + forced
    preemptions).  Warm retention genuinely engages only for qwen +
    paged + sharing (the only cell with a prefix index); every other
    cell pins the graceful no-op.  Two slots over four requests force
    continuous re-admission, so retention decisions happen mid-run, not
    just at drain."""
    cfg = dataclasses.replace(_paged(get_config(arch, smoke=True)),
                              prefix_sharing=sharing)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    extras = _spec_extras(cfg, 2)

    def run(c):
        reqs = _prefix_requests(cfg)
        eng = ServeEngine(c, params, batch_slots=2, max_len=32,
                          chunk_size=4, extras=extras)
        eng.run(reqs)
        return eng, [list(r.generated) for r in reqs]

    _, ref = run(cfg)
    eng, got = run(_adaptive(cfg, every=1))
    assert got == ref, f"{arch}/sharing={sharing}: adaptation moved tokens"
    if sharing and eng.prefix_sharing:
        assert eng.stats["warm_retained"] >= 1, "warm tier never engaged"
        assert eng.stats["warm_hits"] >= 1, "no re-arrival ever revived"
        assert eng.stats["replans"] >= 1
        # Pin the adaptive report schema — adaptive_rows parses it.
        rep = eng.policy_report()["adaptive"]
        assert set(rep) == {
            "enabled", "warm_tier", "warm_pages_now", "warm_retained",
            "warm_reclaimed", "warm_hits", "warm_tokens_saved", "replans",
            "wave", "classes", "combos", "warm_budget",
        }
        assert rep["enabled"] and rep["warm_tier"]
    else:
        assert eng.stats["warm_retained"] == 0
    _assert_warm_conserved(eng)

    chaos = dataclasses.replace(
        _adaptive(cfg, every=1), chaos_alloc_fail_p=0.3,
        chaos_preempt_p=0.3, chaos_seed=3,
    )
    eng_c, got_c = run(chaos)
    assert got_c == ref, f"{arch}/sharing={sharing}: chaos+adaptive diverged"
    _assert_warm_conserved(eng_c)


def test_adaptive_cost_aware_preemption_identity():
    """Cost-aware victim selection under genuine page pressure: the
    adaptive engine may evict a DIFFERENT resident than youngest-first,
    but recompute-restore keeps every stream bit-identical, the
    anti-livelock bound holds, and warm reclaim (capacity beats
    retention) keeps admission unblocked in an undersized pool."""
    cfg = dataclasses.replace(
        _paged(get_config("qwen2.5-32b", smoke=True)), prefix_sharing=True
    )
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    spec = [(6, 6), (10, 8), (5, 8)]

    def run(c, **kw):
        rng = np.random.default_rng(3)
        reqs = [Request(
            prompt=rng.integers(0, cfg.vocab, size=n).astype(np.int32),
            max_new_tokens=m, seed=11) for n, m in spec]
        eng = ServeEngine(c, params, batch_slots=2, max_len=32,
                          chunk_size=2, **kw)
        eng.run(reqs)
        return eng, [list(r.generated) for r in reqs]

    _, ref = run(cfg)                              # roomy pool: no eviction
    eng, got = run(_adaptive(cfg, warm=2, every=1), n_pages=4)
    assert eng.stats["preempted"] >= 1, "scenario failed to force eviction"
    assert got == ref, "cost-aware victim choice changed a stream"
    assert all(r.preempted_n <= 1
               for r in eng._by_id.values()), "anti-livelock bound broken"
    _assert_warm_conserved(eng)


def test_prefix_hit_rate_not_diluted_by_restores():
    """Regression (stats bugfix): prefix_hit_rate used to divide
    prefix_hits by prefill_tokens, which also counts preemption-restore
    recompute prefills — forced preemptions deflated the rate.  The rate
    is now hits-over-FRESH-admissions; restores accrue to `readmitted`
    and leave it untouched."""
    cfg = dataclasses.replace(
        _paged(get_config("qwen2.5-32b", smoke=True)), prefix_sharing=True,
        chaos_preempt_p=0.5, chaos_seed=123,
    )
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    reqs = _prefix_requests(cfg)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32, chunk_size=4)
    eng.run(reqs)
    s = eng.serve_stats()
    assert eng.stats["preempted_forced"] >= 1, "chaos never fired"
    assert s["readmitted"] >= 1
    assert s["admitted_fresh"] == len(reqs)
    assert s["prefix_hits_fresh"] >= 1
    assert s["prefix_hit_rate"] == (
        s["prefix_hits_fresh"] / s["admitted_fresh"]
    )
    # The old denominator counted every prefill (fresh + restore), so it
    # strictly exceeds fresh admissions here — the buggy formula would
    # report a strictly lower rate.
    assert s["prefill_tokens"] > s["admitted_fresh"]
    assert s["prefix_hit_rate"] > s["prefix_hits"] / s["prefill_tokens"]


def test_spec_tokens_per_round_counts_only_spec_tokens():
    """Regression (stats bugfix): spec_tokens_per_round used to divide
    ALL decode_tokens by spec_rounds, so plain-chunk tokens (non-spec
    phases sharing a stats dict, e.g. merged bench legs) inflated the
    metric.  Spec-round-emitted tokens now land in their own counter."""
    cfg = dataclasses.replace(
        get_config("qwen2.5-32b", smoke=True), spec_k=2, spec_ngram=2,
    )
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    extras = _spec_extras(cfg, 2)
    rng = np.random.default_rng(5)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, size=6).astype(np.int32),
                    max_new_tokens=6, seed=1) for _ in range(2)]
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32,
                      chunk_size=2, extras=extras)
    eng.run(reqs)
    s0 = eng.serve_stats()
    assert s0["spec_rounds"] >= 1
    assert s0["spec_tokens"] == s0["decode_tokens"]   # pure-spec run
    assert s0["spec_tokens_per_round"] == (
        s0["spec_tokens"] / s0["spec_rounds"]
    )
    # Simulate the mixed case the old formula got wrong: plain decode
    # tokens landing in the same stats dict (spec disabled mid-run /
    # merged bench legs) must NOT move the per-round figure.
    eng.stats["decode_tokens"] += 100
    s1 = eng.serve_stats()
    assert s1["spec_tokens_per_round"] == s0["spec_tokens_per_round"]
    assert s1["spec_tokens_per_round"] < (
        s1["decode_tokens"] / s1["spec_rounds"]
    )
