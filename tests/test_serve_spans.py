"""The serving engine's own trace: host spans at each phase of a scheduler
tick, read back from the profiler's trace, the prefill-rows counter beside
them, and the named scopes the decode chunk carries into its ops."""
import dataclasses
import functools
import glob

import jax
import numpy as np
import pytest

from repro.models import build_model, get_config
from repro.serve import engine as engine_mod
from repro.serve.engine import Request, ServeEngine
from repro.serve.spans import NAMES, span

PAGE = 8


def _engine(spec_k: int = 0) -> ServeEngine:
    cfg = dataclasses.replace(
        get_config("yi-9b", smoke=True), cache_layout="paged",
        kv_page_size=PAGE, prefix_sharing=True, spec_k=spec_k,
    )
    params = build_model(cfg).init(jax.random.PRNGKey(5))
    return ServeEngine(cfg, params, batch_slots=3, max_len=64, chunk_size=4)


def _serve_traced(eng: ServeEngine, log_dir: str):
    """Two admission waves under the profiler: A and B first, then C, which
    shares A's first two pages while A is still resident."""
    rng = np.random.default_rng(0)
    a = rng.integers(0, eng.cfg.vocab, 20).astype(np.int32)
    b = rng.integers(0, eng.cfg.vocab, 11).astype(np.int32)
    c = np.concatenate([a[:2 * PAGE], rng.integers(0, eng.cfg.vocab, 7)
                        ]).astype(np.int32)
    reqs = [Request(prompt=a, max_new_tokens=16, id="A"),
            Request(prompt=b, max_new_tokens=3, id="B"),
            Request(prompt=c, max_new_tokens=5, id="C")]
    jax.profiler.start_trace(log_dir)
    try:
        eng.submit(reqs[:2])
        eng.step()
        eng.submit(reqs[2:])
        eng.drain()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    events.append((e.start_ns, e.start_ns + e.duration_ns,
                                   e.name, dict(e.stats)))
    return reqs, sorted(events)


def _inside(outer, spans):
    return [s for s in spans if outer[0] <= s[0] and s[1] <= outer[1]
            and s is not outer]


@pytest.fixture(scope="module", params=[0, 2], ids=["chunk", "spec"])
def traced(request, tmp_path_factory):
    eng = _engine(spec_k=request.param)
    reqs, events = _serve_traced(eng, str(tmp_path_factory.mktemp("trace")))
    return eng, reqs, events


def test_every_step_holds_its_phases(traced):
    eng, reqs, events = traced
    assert all(r.done for r in reqs)
    assert {e[2] for e in events} <= set(NAMES)
    steps = [e for e in events if e[2] == "serve.step"]
    assert len(steps) >= 3
    waves = 0
    for step in steps:
        inner = {e[2] for e in _inside(step, events)}
        assert {"serve.sweep", "serve.admit", "serve.decode",
                "serve.decode.sync", "serve.emit"} <= inner
        if "serve.prefill" in inner:
            waves += 1
            assert {"serve.prefill.sync", "serve.admit.request"} <= inner
    assert waves == eng.stats["admission_waves"] == 2
    # Nothing lies outside a step: the engine was only ever stepped.
    assert all(any(_inside(s, [e]) for s in steps) for e in events
               if e[2] != "serve.step")


def test_admitted_requests_carry_their_ids(traced):
    eng, reqs, events = traced
    admitted = {e[3]["id"]: e[3] for e in events
                if e[2] == "serve.admit.request"}
    assert set(admitted) == {"A", "B", "C"}
    for r in reqs:
        assert admitted[r.id]["prefix"] == r.prefix_tokens
        assert admitted[r.id]["suffix"] == len(r.prompt) - r.prefix_tokens
    assert admitted["C"]["prefix"] == 2 * PAGE


def test_prefill_rows_counter_matches_the_waves(traced):
    eng, reqs, events = traced
    rows = [e[3]["rows"] for e in events if e[2] == "serve.prefill"]
    stats = eng.serve_stats()
    assert stats["prefill_rows_computed"] == sum(rows)
    assert stats["prefill_rows_computed"] >= stats["prefill_work_tokens"]
    # Wave 1 prefills A's 20 tokens and B's 11, each at its own bucket;
    # wave 2 C's 7-token suffix.  No other slot's rows are computed.
    pad = functools.partial(engine_mod._pad_bucket, cap=64)
    assert rows == [pad(20) + pad(11), pad(7)]


def test_unknown_span_name_is_refused():
    with pytest.raises(ValueError):
        span("serve.nothing")


def test_decode_chunk_ops_carry_the_model_scopes():
    eng = _engine()
    # The compiled program's op metadata, which the profiler reports:
    # op_name="jit(_chunk_fn)/while/body/.../attn/scatter".
    text = eng._decode_chunk.lower(
        eng.params, eng.cache, eng.cur_tok, eng.remaining, eng.tok_idx,
        eng.hist, eng.hist_len, eng.seeds).compile().as_text()
    for scope in ("attn", "mlp", "unembed", "sample"):
        assert f"/{scope}/" in text, scope
