#!/usr/bin/env python3
"""Chip smoke test: the system's main path on a TPU, at full model width.

    python chip_smoke.py [--seed N]             # one chip
    python chip_smoke.py --chips 4 [--seed N]   # four chips

One chip: minicpm-2b at its published widths (40 layers, d_model 2304, 36
heads x 64, d_ff 5760, vocab 122753, bf16) with random weights from
``--seed`` is served through ``ServeEngine`` on a paged KV pool: eight
greedy requests (prompt lengths drawn in 64..512, 32 new tokens each), once
with the XLA decode attention and once with the paged Pallas kernel.  Every
stream is checked against one cache-free ``model.forward`` over prompt +
generated tokens (``repro.serve.verify``).

Four chips: three AdamW steps of minicpm-2b at full width, cut to 4 layers,
on a (data=2, model=2) mesh with ZeRO-1 optimizer state, compared with the
same steps on one device (batch 8 x seq 512 from ``SyntheticLM``).

Nothing here is a benchmark: the times printed include compilation.  The
script refuses to run on anything but a TPU, reads no file, and prints as
its last line ``{"ok": true, "device": {...}}`` naming the devices used.
JAX's persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, or else to ``.jax_cache/`` beside this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "minicpm-2b"
# Serving phase: 8 slots x 1024 positions of paged KV (about 3.0 GB at
# minicpm-2b's 368,640 bytes per token) beside 5.45 GB of bf16 weights.
SLOTS, MAX_LEN, CHUNK, PAGE = 8, 1024, 8, 16
N_REQUESTS, PROMPT_MIN, PROMPT_MAX, NEW_TOKENS = 8, 64, 512, 32
DECODE_KERNELS = ("xla", "pallas_paged")
# A served token may sit at most this many bf16 spacings (at the row's
# largest logit) below the reference maximum; see repro.serve.verify.
MARGIN_ULP = 8.0
# Four-chip phase.
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_LR = 4, 8, 512, 3, 1e-3
# Sharded vs single-device agreement: each step's loss to this relative
# error, and the params' total update (after - before) to this relative L1
# distance.  AdamW's first steps move each weight by about +-lr whatever
# the gradient's size, so a weight whose tiny gradient changes sign under a
# different reduction order moves the other way; that stays a small share
# of all weights, while a wrong gradient reduction moves most of them.
LOSS_RTOL, UPDATE_REL_L1 = 2e-3, 0.05


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Backend compile seconds and persistent-cache hits/misses, from JAX's
    own monitoring events (a cache hit's retrieval counts as its compile)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.events: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **_):
        if name.startswith("/jax/compilation_cache/cache_"):
            key = name.rsplit("/", 1)[1]
            self.events[key] = self.events.get(key, 0) + 1

    def summary(self) -> str:
        return (f"compile_s={self.seconds:.1f} "
                f"cache_hits={self.events.get('cache_hits', 0)} "
                f"cache_misses={self.events.get('cache_misses', 0)}")


def use_compile_cache(jax) -> str:
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; only without it does the
    cache go to a fixed directory of the checkout (the path is part of the
    cache key, so it never moves)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_prompts(seed: int, vocab: int, n: int, lo: int, hi: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, size=n)
    return [rng.integers(0, vocab, size=int(k), dtype=np.int32) for k in lens]


def serve(cfg, params, prompts, *, slots, max_len, chunk, new_tokens):
    """One wave of greedy requests through ServeEngine; returns the engine
    (for its reports) and the finished requests."""
    from repro.serve.engine import Request, ServeEngine

    engine = ServeEngine(cfg, params, batch_slots=slots, max_len=max_len,
                         chunk_size=chunk)
    reqs = [Request(prompt=p, max_new_tokens=new_tokens, id=f"r{i}")
            for i, p in enumerate(prompts)]
    engine.run(reqs)
    return engine, reqs


def check(model, params, reqs, *, width, margin_ulp):
    """Teacher-forced check of every served stream."""
    from repro.serve.verify import check_stream, make_gap_fn

    gap_fn = make_gap_fn(model)
    return [check_stream(gap_fn, params, r.prompt, r.generated, width=width,
                         margin_ulp=margin_ulp) for r in reqs]


def serve_phases(jax, seed: int, cfg, *, slots=SLOTS, max_len=MAX_LEN,
                 prompt_min=PROMPT_MIN, prompt_max=PROMPT_MAX) -> bool:
    """Serve one wave on each decode path and check every stream."""
    from repro.models import build_model

    dev = jax.devices()[0]
    cfg = dataclasses.replace(cfg, cache_layout="paged", kv_page_size=PAGE)
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    log(f"[init] {cfg.arch}: {n_params} params, "
        f"set-up {time.perf_counter() - t0:.1f}s")
    prompts = make_prompts(seed, cfg.vocab, N_REQUESTS, prompt_min,
                           prompt_max)
    log(f"[requests] {N_REQUESTS} greedy, prompt lengths "
        f"{[len(p) for p in prompts]}, {NEW_TOKENS} new tokens each")

    ok = True
    streams = {}
    for kernel in DECODE_KERNELS:
        t0 = time.perf_counter()
        engine, reqs = serve(
            dataclasses.replace(cfg, decode_kernel=kernel), params, prompts,
            slots=slots, max_len=max_len, chunk=CHUNK, new_tokens=NEW_TOKENS,
        )
        served = sum(len(r.generated) for r in reqs)
        log(f"[serve:{kernel}] tokens served {served} in "
            f"{time.perf_counter() - t0:.1f}s (compilation included); "
            f"decode_attention {engine.policy_report()['decode_attention']}")
        del engine
        gc.collect()
        t0 = time.perf_counter()
        results = check(model, params, reqs,
                        width=prompt_max + NEW_TOKENS, margin_ulp=MARGIN_ULP)
        worst = max(c.gaps_ulp.max() for c in results)
        exact = sum(c.exact for c in results)
        passed = (all(c.ok for c in results)
                  and all(len(r.generated) == NEW_TOKENS for r in reqs))
        log(f"[check:{kernel}] {'pass' if passed else 'FAIL'}: "
            f"{sum(c.ok for c in results)}/{len(results)} streams within "
            f"{MARGIN_ULP:g} ulp, {exact}/{served} tokens are the exact "
            f"argmax, worst gap {worst:.2f} ulp "
            f"({time.perf_counter() - t0:.1f}s)")
        ok &= passed
        streams[kernel] = [list(r.generated) for r in reqs]
    same = sum(a == b for a, b in zip(*streams.values()))
    log(f"[paths] {same}/{N_REQUESTS} streams identical across "
        f"{' and '.join(DECODE_KERNELS)}")
    stats = dev.memory_stats() or {}
    log(f"[memory] peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    return ok


def train_phases(jax, seed: int, cfg, *, batch=TRAIN_BATCH,
                 seq=TRAIN_SEQ) -> bool:
    """TRAIN_STEPS sharded steps on a (2, 2) mesh against one device."""
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.data.pipeline import SyntheticLM
    from repro.distributed import sharding as sh
    from repro.launch.mesh import make_local_mesh
    from repro.train import optimizer as opt
    from repro.train.step import (
        TrainConfig, init_train_state, make_train_step, state_shardings,
    )

    tcfg = TrainConfig(
        adamw=opt.AdamWConfig(lr=TRAIN_LR, warmup_steps=0,
                              total_steps=TRAIN_STEPS),
        zero1=True, batch_axes=("data",),
    )
    train_step, model = make_train_step(cfg, tcfg)
    data = SyntheticLM(cfg, batch=batch, seq=seq, seed=seed)
    batches = [data(i) for i in range(TRAIN_STEPS)]
    t0 = time.perf_counter()
    host0 = jax.device_get(jax.jit(lambda k: init_train_state(model, k))(
        jax.random.PRNGKey(seed)))
    log(f"[init] {cfg.arch} x {cfg.n_layers} layers: "
        f"{sum(x.size for x in jax.tree_util.tree_leaves(host0['params']))} "
        f"params, set-up {time.perf_counter() - t0:.1f}s")

    def run(step_fn, state, place_batch):
        losses = []
        for b in batches:
            state, metrics = step_fn(state, place_batch(b))
            losses.append(metrics["loss"])
        losses, params = jax.device_get((losses, state["params"]))
        return [float(x) for x in losses], params

    t0 = time.perf_counter()
    one = jax.devices()[0]
    ref_losses, ref_params = run(
        jax.jit(train_step, donate_argnums=0),
        jax.device_put(host0, one), lambda b: jax.device_put(b, one),
    )
    log(f"[train:1 device] losses {ref_losses} "
        f"({time.perf_counter() - t0:.1f}s)")

    mesh = make_local_mesh(data=2, model=2)
    sshard = state_shardings(cfg, mesh, host0, zero1=True)
    bspec = sh.batch_spec(cfg, mesh, batch)
    bshard = {k: NamedSharding(mesh, bspec[k]) for k in batches[0]}
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        step_fn = jax.jit(train_step, in_shardings=(sshard, bshard),
                          out_shardings=(sshard, None), donate_argnums=0)
        losses, params = run(step_fn, jax.device_put(host0, sshard),
                             lambda b: jax.device_put(b, bshard))
    log(f"[train:mesh data=2 model=2] losses {losses} "
        f"({time.perf_counter() - t0:.1f}s)")
    for d in mesh.devices.flat:
        stats = d.memory_stats() or {}
        log(f"[memory] {d}: bytes_in_use {stats.get('bytes_in_use')} "
            f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")

    loss_err = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    # Host arrays from here on: the updates each run made, after - before.
    upd = [[x.astype(np.float32) - p.astype(np.float32) for x, p in zip(
        jax.tree_util.tree_leaves(tree),
        jax.tree_util.tree_leaves(host0["params"]))]
        for tree in (params, ref_params)]
    upd_err = (
        sum(np.abs(a - b).sum() for a, b in zip(*upd))  # repro-lint: disable=R001 -- host arrays
        / sum(np.abs(b).sum() for b in upd[1]))  # repro-lint: disable=R001 -- host arrays
    ok = loss_err <= LOSS_RTOL and upd_err <= UPDATE_REL_L1
    log(f"[check:train] {'pass' if ok else 'FAIL'}: worst loss rel err "
        f"{loss_err:.2e} (limit {LOSS_RTOL:g}), update rel L1 "
        f"{upd_err:.2e} (limit {UPDATE_REL_L1:g})")
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    from repro import hw
    from repro.models import get_config

    hw.chip_for_device(dev)     # a TPU no hw entry describes is an error
    cache = use_compile_cache(jax)
    compiles = CompileLog(jax)
    log(f"[device] {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        ok = train_phases(jax, args.seed, dataclasses.replace(
            get_config(ARCH), n_layers=TRAIN_LAYERS))
    else:
        ok = serve_phases(jax, args.seed, get_config(ARCH))
    log(f"[done] {time.perf_counter() - t0:.1f}s, {compiles.summary()}")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
