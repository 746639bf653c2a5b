"""Serve a small model with batched requests through the device-resident
continuous-batching engine.

The first run through the engine pays jit compilation for the prefill and
the chunked decode loop; timing that run reports compile time, not serving
throughput.  We warm up first, then time a fresh request wave on the same
(already-compiled) engine and report both TTFT and steady-state tok/s.

``--paged`` serves the same wave through the paged KV pool (half the
contiguous reservation) and checks the outputs are identical.

``--spec`` serves the same wave through the speculative engine (n-gram
draft + chunked verification), checks the outputs are identical, and
prints the draft acceptance rate.  On the tiny smoke model per-eval
compute is negligible, so the interesting numbers here are acceptance and
tokens/round — the throughput win shows up at serving-scale dims
(``benchmarks.serve_bench.spec_rows``).

``--shared-prefix`` serves a many-slots-one-system-prompt wave through
the paged pool with prefix sharing on and off: attached requests ride the
resident system-prompt pages (refcounted; prefilled once) and the shared
engine holds far fewer pages at its peak, with identical outputs.

``--deadline-s S`` attaches a per-request deadline to a long-budget wave:
requests that blow it are expired mid-stream (partial tokens kept, slot
and pages freed) and counted against goodput-under-deadline.

``--cancel`` cancels one resident request mid-stream after the first
decode chunk: the engine retires it at the next chunk boundary, keeps the
tokens already emitted, and the rest of the wave is unaffected.

``--crash`` demonstrates crash-safe serving (DESIGN.md §5.6): a
journal-armed engine snapshots mid-wave and then dies on an injected
``ChaosCrash``; a FRESH engine restores from the snapshot + journal
suffix, finishes the wave, and the streams are bit-identical to an
uninterrupted run with zero leaked pages.

Run:  PYTHONPATH=src python examples/serve_decode.py
          [--paged] [--spec] [--shared-prefix] [--deadline-s S] [--cancel]
          [--crash]
"""
import dataclasses
import os
import sys
import tempfile
import time

import jax
import numpy as np

from repro.models import build_model, get_config
from repro.serve.engine import Request, ServeEngine


def make_requests(cfg, rng, n_tokens=12):
    return [
        Request(prompt=rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                max_new_tokens=n_tokens)
        for n in (5, 8, 3, 6, 9, 4)
    ]


def main():
    cfg = get_config("qwen2.5-32b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    serve = ServeEngine(cfg, params, batch_slots=4, max_len=64, chunk_size=8)
    rng = np.random.default_rng(0)

    # Warm-up: compiles prefill + chunked decode (not timed).
    t0 = time.perf_counter()
    serve.run(make_requests(cfg, rng))
    print(f"warm-up (includes jit compile): {time.perf_counter() - t0:.2f}s")

    # Timed: steady-state serving on the compiled engine.
    reqs = make_requests(cfg, rng)
    base_stats = dict(serve.stats)
    t0 = time.perf_counter()
    serve.run(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(r.generated) for r in reqs)
    ttft = np.mean([r.ttft_s for r in reqs])
    syncs = serve.stats["host_syncs"] - base_stats["host_syncs"]
    print(f"generated {total} tokens across {len(reqs)} requests in {dt:.3f}s")
    print(f"steady-state: {total / dt:.0f} tok/s, mean TTFT {ttft * 1e3:.1f}ms, "
          f"{syncs} host syncs ({syncs / total:.3f}/token)")
    for i, r in enumerate(reqs):
        print(f"req{i}: prompt[{len(r.prompt)}] -> {r.generated}")

    if "--paged" in sys.argv:
        # Same wave through the paged pool at half the contiguous
        # reservation (4 slots x 64 = 256 positions -> 8 pages x 16 = 128).
        paged_cfg = dataclasses.replace(
            cfg, cache_layout="paged", kv_page_size=16
        )
        pserve = ServeEngine(paged_cfg, params, batch_slots=4, max_len=64,
                             chunk_size=8, n_pages=8)
        prng = np.random.default_rng(0)       # replays the contiguous waves
        pserve.run(make_requests(cfg, prng))  # warm-up (same first wave)
        preqs = make_requests(cfg, prng)      # same prompts as timed `reqs`
        t0 = time.perf_counter()
        pserve.run(preqs)
        dt = time.perf_counter() - t0
        ptotal = sum(len(r.generated) for r in preqs)
        print(f"paged pool (128/256 positions): {ptotal / dt:.0f} tok/s")
        assert all(a.generated == b.generated for a, b in zip(reqs, preqs))
        print("paged == contiguous: True")

    if "--shared-prefix" in sys.argv:
        # Many slots, one system prompt: the prefix-hit workload.  With
        # sharing, the 32-token system prompt (2 pages of 16) prefills
        # once; every later admission attaches to its resident pages and
        # prefills only the few-token user tail.
        rng2 = np.random.default_rng(1)
        sys_p = rng2.integers(0, cfg.vocab, size=32).astype(np.int32)

        def sys_requests():
            r = np.random.default_rng(2)
            return [
                Request(prompt=np.concatenate(
                    [sys_p, r.integers(0, cfg.vocab, size=n).astype(np.int32)]),
                    max_new_tokens=12)
                for n in (5, 8, 3, 6, 9, 4)
            ]

        paged_cfg = dataclasses.replace(
            cfg, cache_layout="paged", kv_page_size=16
        )
        outs = {}
        for name, c in (
            ("unshared", paged_cfg),
            ("shared", dataclasses.replace(paged_cfg, prefix_sharing=True)),
        ):
            xeng = ServeEngine(c, params, batch_slots=4, max_len=64,
                               chunk_size=8)
            xreqs = sys_requests()
            xeng.run(xreqs)
            outs[name] = [r.generated for r in xreqs]
            stats = xeng.serve_stats()
            print(f"{name}: peak {xeng.stats['peak_pages_held']}/"
                  f"{xeng.n_pages} pages held, "
                  f"{stats['prefix_hits']} prefix hits "
                  f"({stats['prefix_tokens_shared']} prompt tokens attached "
                  "from resident pages)")
        assert outs["shared"] == outs["unshared"]
        print("shared prefix == unshared: True")

    if "--spec" in sys.argv:
        # Same wave through the speculative path: n-gram drafts verified in
        # chunks of spec_k + 1, rejected suffixes rolled back per slot.
        spec_cfg = dataclasses.replace(cfg, spec_k=4, spec_ngram=3)
        sserve = ServeEngine(spec_cfg, params, batch_slots=4, max_len=64,
                             chunk_size=10)
        srng = np.random.default_rng(0)       # replays the contiguous waves
        sserve.run(make_requests(cfg, srng))  # warm-up (same first wave)
        sreqs = make_requests(cfg, srng)      # same prompts as timed `reqs`
        t0 = time.perf_counter()
        sserve.run(sreqs)
        dt = time.perf_counter() - t0
        stotal = sum(len(r.generated) for r in sreqs)
        stats = sserve.serve_stats()
        print(f"speculative (k=4): {stotal / dt:.0f} tok/s, "
              f"acceptance {stats['spec_acceptance_rate']:.2f}, "
              f"{stats['spec_tokens_per_round']:.2f} tokens/round")
        assert all(a.generated == b.generated for a, b in zip(reqs, sreqs))
        print("speculative == plain: True")

    deadline = None
    if "--deadline-s" in sys.argv:
        deadline = float(sys.argv[sys.argv.index("--deadline-s") + 1])
    if deadline is not None or "--cancel" in sys.argv:
        # Lifecycle demo (DESIGN.md §5.5): submit/step/cancel/drain by
        # hand instead of run(), since cancellation is a mid-stream act.
        lrng = np.random.default_rng(5)
        lreqs = [
            Request(prompt=lrng.integers(0, cfg.vocab, size=n)
                    .astype(np.int32),
                    max_new_tokens=24, deadline_s=deadline, id=f"demo-{i}")
            for i, n in enumerate((5, 8, 3, 6))
        ]
        leng = ServeEngine(cfg, params, batch_slots=2, max_len=64,
                           chunk_size=4)
        leng.submit(lreqs)
        leng.step()                        # admit first wave + one chunk
        if "--cancel" in sys.argv:
            victim = next(r for r in lreqs if r.status == "resident")
            assert leng.cancel(victim.id)
            print(f"cancel({victim.id}) requested mid-stream "
                  f"({len(victim.generated)} tokens emitted so far)")
        leng.drain()
        print("lifecycle:", {r.id: f"{r.status}[{len(r.generated)}]"
                             for r in lreqs})
        print(f"cancelled={leng.stats['cancelled']} "
              f"expired={leng.stats['expired']} "
              "goodput_under_deadline="
              f"{leng.serve_stats()['goodput_under_deadline']:.2f}")

    if "--crash" in sys.argv:
        # Crash-safe serving demo (DESIGN.md §5.6).  Reference: the same
        # wave served uninterrupted through a paged engine.
        from repro.serve.chaos import ChaosCrash

        paged_cfg = dataclasses.replace(
            cfg, cache_layout="paged", kv_page_size=16
        )
        crng = np.random.default_rng(0)
        ref_eng = ServeEngine(paged_cfg, params, batch_slots=4, max_len=64,
                              chunk_size=8, n_pages=8)
        ref_eng.run(make_requests(cfg, crng))
        ref_out = ref_eng.results()

        tmp = tempfile.mkdtemp(prefix="serve-crash-demo-")
        jpath = os.path.join(tmp, "requests.jsonl")
        spath = os.path.join(tmp, "engine.json")
        crash_cfg = dataclasses.replace(paged_cfg, chaos_crash_after_wave=2)
        doomed = ServeEngine(crash_cfg, params, batch_slots=4, max_len=64,
                             chunk_size=8, n_pages=8, journal_path=jpath)
        crng = np.random.default_rng(0)
        wave = make_requests(cfg, crng)
        doomed.submit(wave[:4])
        doomed.step()
        info = doomed.snapshot(spath)
        print(f"snapshot: {info['requests']} request records "
              f"({info['in_flight']} in flight) -> {spath}")
        doomed.submit(wave[4:])            # journaled past the snapshot
        try:
            doomed.drain()
        except ChaosCrash as c:
            print(f"injected crash after admission wave {c.wave} "
                  "(journal flushed at the chunk boundary)")

        fresh = ServeEngine(paged_cfg, params, batch_slots=4, max_len=64,
                            chunk_size=8, n_pages=8, journal_path=jpath)
        rep = fresh.restore(spath)
        print(f"restore: {rep['restored']} re-queued, "
              f"{rep['replayed_events']} journal events replayed, "
              f"{rep['terminal']} already terminal")
        fresh.drain()
        assert fresh.results() == ref_out
        assert sorted(fresh.free_pages) == list(range(fresh.n_pages))
        print("recovered == uninterrupted: True (zero leaked pages)")


if __name__ == "__main__":
    main()
