"""Request-lifecycle + chaos tests (DESIGN.md §5.5).

The tentpole claims: (1) preemption under genuine page pressure restores
evicted requests BIT-IDENTICALLY (the `(seed, token index)` sampler keys
make the recompute-prefill over prompt + emitted reproduce the stream by
construction); (2) cancellation/deadlines free slots, pages and trie refs
mid-stream with nothing leaked; (3) seeded fault injection
(`serve.chaos`) — alloc refusals and forced preemptions — perturbs the
schedule but never the outputs, with `check_invariants()` holding after
every wave (the engine asserts it automatically whenever a chaos knob is
armed).  A hypothesis state machine drives a REAL tiny engine through
random submit/cancel/step interleavings with the invariant checked after
every step.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.models import build_model, get_config
from repro.serve.engine import AdmissionReject, Request, ServeEngine


def _paged(cfg, page_size=8):
    return dataclasses.replace(
        cfg, cache_layout="paged", kv_page_size=page_size
    )


def _reqs(cfg, spec, seed=0, rng_seed=3):
    """Fresh Request objects for (prompt_len, max_new_tokens) pairs —
    identity comparisons need two independent copies of one workload."""
    rng = np.random.default_rng(rng_seed)
    return [
        Request(prompt=rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                max_new_tokens=m, seed=seed)
        for n, m in spec
    ]


# (prompt_len, max_new_tokens) sized for page_size=8 / max_len=32:
# A needs 2 pages (11 positions), B needs 3 (17), C needs 2 (12).  With
# n_pages=4 B's admission is gated behind resident A and must preempt it.
_PRESSURE = [(6, 6), (10, 8), (5, 8)]


def _run_engine(cfg, params, reqs, **kw):
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32,
                      chunk_size=2, **kw)
    eng.run(reqs)
    return eng


@pytest.mark.parametrize("sampling", ["greedy", "top_p"])
@pytest.mark.parametrize("sharing", [False, True])
def test_preemption_identity_matrix(sampling, sharing):
    """Acceptance gate: an undersized pool forces >= 1 preemption, and
    every request's stream is bit-identical to the uninterrupted run —
    across {greedy, seeded top-p} x {prefix sharing on, off}."""
    cfg = get_config("yi-9b", smoke=True)
    if sampling == "top_p":
        cfg = dataclasses.replace(cfg, sampling="top_p", top_p=0.9)
    cfg = dataclasses.replace(_paged(cfg), prefix_sharing=sharing)
    params = build_model(cfg).init(jax.random.PRNGKey(0))

    # Reference: same workload, pool big enough that nothing is evicted.
    ref = _reqs(cfg, _PRESSURE, seed=11)
    eng_ref = _run_engine(cfg, params, ref)
    assert eng_ref.stats["preempted"] == 0

    got = _reqs(cfg, _PRESSURE, seed=11)
    eng = _run_engine(cfg, params, got, n_pages=4)
    assert eng.stats["preempted"] >= 1, "scenario failed to force eviction"
    assert eng.stats["recompute_tokens"] >= 1
    for r, rr in zip(got, ref):
        assert r.done and r.status == "finished"
        assert len(r.generated) == r.max_new_tokens
        assert r.generated == rr.generated, (
            f"preempted stream diverged (preempted_n={r.preempted_n})"
        )
    # Nothing leaked: the full pool is free and state is conserved.
    assert sorted(eng.free_pages) == list(range(eng.n_pages))
    eng.check_invariants()


def test_preemption_is_bounded_and_refcount_safe():
    """Natural preemption evicts each request at most once (the
    never-preempted-victim guard), and under prefix sharing the victim's
    shared pages are only dereferenced — the sharer keeps decoding from
    intact storage."""
    cfg = dataclasses.replace(
        _paged(get_config("yi-9b", smoke=True)), prefix_sharing=True
    )
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    # A and B share a 2-page (16-token) prompt prefix and admit together;
    # C's demand (3 pages vs 1 free) then evicts B — the YOUNGEST — while
    # its shared prefix pages are still referenced by resident A.
    rng = np.random.default_rng(9)
    common = rng.integers(0, cfg.vocab, size=17).astype(np.int32)
    reqs = [
        Request(prompt=common, max_new_tokens=6, seed=1),       # 3 pages
        Request(prompt=np.concatenate(
            [common, rng.integers(0, cfg.vocab, size=3).astype(np.int32)]
        ), max_new_tokens=6, seed=2),      # 4 pages, 2 shared with A
        Request(prompt=rng.integers(0, cfg.vocab, size=10).astype(np.int32),
                max_new_tokens=8, seed=3),                      # 3 pages
    ]
    ref = [dataclasses.replace(r, generated=[]) for r in reqs]
    eng_ref = ServeEngine(cfg, params, batch_slots=3, max_len=32,
                          chunk_size=2)
    eng_ref.run(ref)
    eng = ServeEngine(cfg, params, batch_slots=3, max_len=32,
                      chunk_size=2, n_pages=6)
    eng.run(reqs)
    assert eng.stats["preempted"] >= 1
    assert all(r.preempted_n <= 1 for r in reqs)
    for r, rr in zip(reqs, ref):
        assert r.generated == rr.generated
    assert sorted(eng.free_pages) == list(range(eng.n_pages))
    eng.check_invariants()


@pytest.mark.parametrize("arch", ["yi-9b", "mamba2-1.3b"])
def test_chaos_forced_preemption_identity(arch):
    """cfg.chaos_preempt_p force-evicts residents at wave boundaries —
    including on layouts where genuine page pressure cannot arise
    (mamba2 falls back to contiguous).  Streams must stay bit-identical
    and, because a chaos knob is armed, the engine asserts
    check_invariants() after every single wave."""
    cfg = get_config(arch, smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(1))
    spec = [(5, 6), (3, 5), (7, 4), (4, 6)]
    ref = _reqs(cfg, spec, seed=5)
    _run_engine(cfg, params, ref)
    chaos_cfg = dataclasses.replace(
        cfg, chaos_preempt_p=0.5, chaos_seed=123
    )
    got = _reqs(chaos_cfg, spec, seed=5)
    eng = _run_engine(chaos_cfg, params, got)
    assert eng.stats["preempted_forced"] >= 1, "chaos never fired"
    for r, rr in zip(got, ref):
        assert r.generated == rr.generated, "forced preemption changed output"
    eng.check_invariants()


def test_chaos_alloc_failures_identity_and_zero_leaks():
    """Seeded alloc refusals are indistinguishable from pool exhaustion:
    the run must stay bit-identical to the fault-free run and end with
    the entire pool back on the free list (the CI chaos leg's gate)."""
    cfg = _paged(get_config("yi-9b", smoke=True))
    params = build_model(cfg).init(jax.random.PRNGKey(2))
    spec = [(6, 6), (9, 5), (5, 7), (4, 4)]
    ref = _reqs(cfg, spec, seed=7)
    _run_engine(cfg, params, ref, n_pages=6)
    # Seed chosen so injections actually fire within the handful of
    # allocs this workload makes (default_rng(0) draws 0.27, 0.04, 0.02
    # early — three refusals at p=0.4).
    chaos_cfg = dataclasses.replace(
        cfg, chaos_alloc_fail_p=0.4, chaos_seed=0
    )
    got = _reqs(chaos_cfg, spec, seed=7)
    eng = _run_engine(chaos_cfg, params, got, n_pages=6)
    assert eng.allocator.injected_failures >= 1, "chaos never fired"
    for r, rr in zip(got, ref):
        assert r.generated == rr.generated, "injected fault changed output"
    assert sorted(eng.free_pages) == list(range(eng.n_pages))
    eng.check_invariants()


def test_chaos_allocator_seeded_and_atomic():
    """ChaosAllocator unit behavior (hypothesis-free so it always runs;
    the interleaving machine lives in test_alloc_property): identical
    seeds reproduce the exact injection pattern, an injected refusal
    changes no allocator state, and alloc(0) — the fully-shared-prefix
    no-op — is never injected."""
    from repro.serve.chaos import ChaosAllocator

    def pattern(seed):
        alloc = ChaosAllocator(8, fail_p=0.5, seed=seed)
        out = []
        for _ in range(12):
            free_before = alloc.free_pages
            refs_before = {p: alloc.ref_count(p) for p in alloc.held_pages}
            ids = alloc.alloc(1)
            out.append(ids is None)
            if ids is None:
                assert alloc.last_injected    # pool never genuinely empty
                assert alloc.free_pages == free_before
                assert {p: alloc.ref_count(p)
                        for p in alloc.held_pages} == refs_before
            else:
                alloc.release(ids)
        return out

    assert pattern(3) == pattern(3)          # reproducible from the seed
    assert any(pattern(3)) and not all(pattern(3))
    assert pattern(3) != pattern(4)          # and actually seed-dependent

    alloc = ChaosAllocator(4, fail_p=1.0 - 1e-12, seed=0)
    for _ in range(32):
        assert alloc.alloc(0) == []          # never injected for n == 0
        assert not alloc.last_injected
    assert alloc.injected_failures == 0


def test_cancel_queued_and_resident():
    """cancel() retires a queued request before it ever runs and a
    resident one mid-stream (slot + pages free, partial tokens kept);
    unknown or already-terminal ids return False instead of raising."""
    cfg = _paged(get_config("yi-9b", smoke=True))
    params = build_model(cfg).init(jax.random.PRNGKey(3))
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=32, chunk_size=2)
    rng = np.random.default_rng(1)
    mk = lambda rid: Request(  # noqa: E731
        prompt=rng.integers(0, cfg.vocab, size=4).astype(np.int32),
        max_new_tokens=12, id=rid,
    )
    resident, queued = mk("res"), mk("qd")
    eng.submit([resident, queued])
    assert eng.step()                       # admits "res", decodes a chunk
    assert resident.status == "resident" and len(resident.generated) >= 1
    assert eng.cancel("qd") and eng.cancel("res")
    assert not eng.cancel("no-such-id")
    eng.drain()
    assert queued.done and queued.status == "cancelled"
    assert queued.generated == []           # never admitted
    assert resident.done and resident.status == "cancelled"
    assert 1 <= len(resident.generated) < resident.max_new_tokens
    assert not eng.cancel("res")            # terminal: idempotent False
    assert eng.stats["cancelled"] == 2
    assert sorted(eng.free_pages) == list(range(eng.n_pages))
    eng.check_invariants()


def test_deadline_and_queue_wait_expiry():
    """deadline_s expires a resident mid-stream (partial tokens kept) and
    max_queue_wait_s expires a stale queued request; both count against
    goodput-under-deadline in serve_stats()/policy_report()."""
    import time

    cfg = _paged(get_config("yi-9b", smoke=True))
    params = build_model(cfg).init(jax.random.PRNGKey(4))
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=32, chunk_size=2)
    rng = np.random.default_rng(2)
    p = lambda: rng.integers(0, cfg.vocab, size=4).astype(np.int32)  # noqa: E731
    slow = Request(prompt=p(), max_new_tokens=24, deadline_s=0.05, id="slow")
    stale = Request(prompt=p(), max_new_tokens=4, max_queue_wait_s=1e-6,
                    id="stale")
    ok = Request(prompt=p(), max_new_tokens=2, deadline_s=60.0, id="ok")
    eng.submit([slow, stale, ok])
    assert eng.step()                       # "slow" resident, decoding
    time.sleep(0.06)                        # blow slow's deadline
    eng.drain()
    assert slow.status == "expired" and 1 <= len(slow.generated) < 24
    assert stale.status == "expired" and stale.generated == []
    assert ok.status == "finished" and len(ok.generated) == 2
    assert eng.stats["expired"] == 2
    st = eng.serve_stats()
    # Deadlined population is {slow, ok} ("stale" carried only a queue-
    # wait bound, no deadline_s): 1 of 2 met.
    assert st["goodput_under_deadline"] == pytest.approx(0.5)
    assert sorted(eng.free_pages) == list(range(eng.n_pages))
    eng.check_invariants()


def test_bounded_queue_backpressure():
    """max_queue rejects the whole over-quota batch with reason
    "queue_full" BEFORE enqueuing anything, and the engine stays usable."""
    cfg = get_config("yi-9b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(5))
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=32,
                      chunk_size=2, max_queue=2)
    rng = np.random.default_rng(3)
    mk = lambda: Request(  # noqa: E731
        prompt=rng.integers(0, cfg.vocab, size=4).astype(np.int32),
        max_new_tokens=2,
    )
    with pytest.raises(AdmissionReject, match="max_queue") as ei:
        eng.submit([mk(), mk(), mk()])
    assert ei.value.reason == "queue_full"
    assert len(eng.queue) == 0              # nothing half-submitted
    assert eng.stats["rejected"] == 3
    batch = [mk(), mk()]
    eng.submit(batch)                       # at quota: accepted
    eng.drain()
    assert all(r.status == "finished" for r in batch)


def test_submit_rejects_impossible_page_demand():
    """Satellite regression: a request whose worst-case page demand
    exceeds the ENTIRE pool used to enqueue and then wedge the FIFO
    head-of-line gate forever; it must be rejected at submit."""
    cfg = _paged(get_config("yi-9b", smoke=True))
    params = build_model(cfg).init(jax.random.PRNGKey(6))
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32,
                      chunk_size=2, n_pages=2)     # pool: 16 positions
    rng = np.random.default_rng(4)
    impossible = Request(
        prompt=rng.integers(0, cfg.vocab, size=10).astype(np.int32),
        max_new_tokens=8,                          # 17 positions -> 3 pages
    )
    fine = Request(
        prompt=rng.integers(0, cfg.vocab, size=6).astype(np.int32),
        max_new_tokens=4,
    )
    with pytest.raises(AdmissionReject, match="could never be admitted") as ei:
        eng.submit([fine, impossible])
    assert ei.value.reason == "pool_too_small"
    assert len(eng.queue) == 0              # batch validation is atomic
    eng.run([fine])                          # engine unharmed
    assert fine.status == "finished"


def test_duplicate_id_rejected():
    cfg = get_config("yi-9b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(7))
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=32)
    prompt = np.arange(4, dtype=np.int32) % cfg.vocab
    eng.run([Request(prompt=prompt, max_new_tokens=2, id="dup")])
    with pytest.raises(AdmissionReject) as ei:
        eng.submit([Request(prompt=prompt, max_new_tokens=2, id="dup")])
    assert ei.value.reason == "duplicate_id"


def test_policy_report_schema_stable():
    """Benches and CI parse policy_report()/serve_stats(); pin the full
    key sets (including the §5.5 lifecycle section) so they can't drift
    silently."""
    cfg = dataclasses.replace(
        _paged(get_config("yi-9b", smoke=True)),
        prefix_sharing=True, spec_k=2,
    )
    params = build_model(cfg).init(jax.random.PRNGKey(8))
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32)
    report = eng.policy_report()
    assert set(report) == {
        "cache_layout", "sampling", "speculative", "paged_kv",
        "prefix_sharing", "adaptive", "lifecycle", "integrity",
        "decode_attention",
    }
    assert set(report["decode_attention"]) == {
        "kernel", "planned_splits", "kernel_bkv",
    }
    assert report["adaptive"] == {"enabled": False}   # static engine
    assert set(report["lifecycle"]) == {
        "preemption_enabled", "max_queue", "preempted", "preempted_forced",
        "recompute_tokens", "cancelled", "expired", "rejected",
        "goodput_under_deadline", "chaos",
    }
    assert set(report["lifecycle"]["chaos"]) == {
        "alloc_fail_p", "preempt_p", "share_fail_p", "corrupt_p",
        "crash_after_wave", "seed", "injected_alloc_failures",
        "injected_share_failures", "injected_corruptions",
    }
    assert set(report["integrity"]) == {
        "enabled", "strict_invariants", "journal", "stamped_pages",
        "quarantined_pages", "corrupted_pages", "healed_requests",
        "snapshots", "restores",
    }
    stats = eng.serve_stats()
    assert {
        "preempted", "preempted_forced", "recompute_tokens", "cancelled",
        "expired", "rejected", "deadline_total", "deadline_met",
        "goodput_under_deadline", "invariant_checks", "integrity_sweeps",
        "corrupted_pages", "healed_requests", "snapshots", "restores",
    } <= set(stats)
    assert stats["goodput_under_deadline"] == 1.0    # vacuous: no SLOs yet


def test_chaos_share_failures_identity_and_zero_leaks():
    """Satellite: seeded SHARE refusals (the alloc-own-then-share
    admission ordering's second failure point) roll back the head's
    fresh allocation atomically — no refcount perturbed — and the run
    stays bit-identical to the fault-free run with zero leaked pages."""
    cfg = dataclasses.replace(
        _paged(get_config("yi-9b", smoke=True)), prefix_sharing=True
    )
    params = build_model(cfg).init(jax.random.PRNGKey(9))
    rng = np.random.default_rng(17)
    common = rng.integers(0, cfg.vocab, size=17).astype(np.int32)
    spec_reqs = lambda: [  # noqa: E731
        Request(prompt=common.copy(), max_new_tokens=5, seed=1),
        Request(prompt=np.concatenate(
            [common, rng.integers(0, cfg.vocab, size=2).astype(np.int32)]
        ), max_new_tokens=5, seed=2),       # attaches to A's prefix pages
        Request(prompt=common.copy(), max_new_tokens=4, seed=3),
    ]
    rng = np.random.default_rng(17)         # same prompts both runs
    ref = spec_reqs()
    rng = np.random.default_rng(17)
    got = spec_reqs()
    _run_engine(cfg, params, ref)
    chaos_cfg = dataclasses.replace(cfg, chaos_share_fail_p=0.6,
                                    chaos_seed=1)
    eng = _run_engine(chaos_cfg, params, got)
    assert eng.allocator.injected_share_failures >= 1, "chaos never fired"
    for r, rr in zip(got, ref):
        assert r.generated == rr.generated, "share refusal changed output"
    assert sorted(eng.free_pages) == list(range(eng.n_pages))
    eng.check_invariants()


def test_chaos_share_refusal_is_atomic():
    """ChaosAllocator.share unit: an injected refusal returns False
    having touched NO refcount, share([]) is never injected, and the
    injection pattern is reproducible from the seed."""
    from repro.serve.chaos import ChaosAllocator

    def pattern(seed):
        alloc = ChaosAllocator(8, fail_p=0.0, seed=seed, share_fail_p=0.5)
        base = alloc.alloc(3)
        out = []
        for _ in range(12):
            refs_before = {p: alloc.ref_count(p) for p in alloc.held_pages}
            ok = alloc.share(base)
            out.append(ok)
            if not ok:
                assert alloc.last_injected
                assert {p: alloc.ref_count(p)
                        for p in alloc.held_pages} == refs_before
            else:
                alloc.release(base)
        return out

    assert pattern(5) == pattern(5)
    assert any(pattern(5)) and not all(pattern(5))
    assert pattern(5) != pattern(6)

    alloc = ChaosAllocator(4, fail_p=0.0, seed=0, share_fail_p=1.0 - 1e-12)
    for _ in range(16):
        assert alloc.share([]) is True       # no-op: never injected
        assert not alloc.last_injected
    assert alloc.injected_share_failures == 0


def test_strict_invariants_runs_without_chaos(monkeypatch):
    """Satellite: cfg.strict_invariants (or the REPRO_STRICT_INVARIANTS
    env var CI sets) arms the per-wave check_invariants() sweep with no
    chaos knob on; without either, no per-wave check runs."""
    cfg = _paged(get_config("yi-9b", smoke=True))
    params = build_model(cfg).init(jax.random.PRNGKey(10))
    reqs = lambda: _reqs(cfg, [(5, 4), (4, 3)], seed=2)  # noqa: E731

    monkeypatch.delenv("REPRO_STRICT_INVARIANTS", raising=False)
    eng = _run_engine(cfg, params, reqs())
    assert eng.stats["invariant_checks"] == 0

    strict = dataclasses.replace(cfg, strict_invariants=True)
    eng = _run_engine(strict, params, reqs())
    assert eng.stats["invariant_checks"] >= 1

    monkeypatch.setenv("REPRO_STRICT_INVARIANTS", "1")
    eng = _run_engine(cfg, params, reqs())
    assert eng.stats["invariant_checks"] >= 1
    monkeypatch.setenv("REPRO_STRICT_INVARIANTS", "0")   # "0" disarms
    eng = _run_engine(cfg, params, reqs())
    assert eng.stats["invariant_checks"] == 0
