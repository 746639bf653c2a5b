"""Admission prefill computes only the admitted requests: each request of a
wave is prefilled alone, at the bucket of its own suffix, into its own slot.
A wave of several requests therefore gives the tokens each request gets when
it is admitted alone, compiles nothing that single-request waves did not,
and counts exactly its requests' rows."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.models import build_model, get_config
from repro.serve.engine import Request, ServeEngine, _pad_bucket

MAX_LEN = 64
LENS = (5, 13, 3, 9)          # buckets 8, 16, 8, 16
BUDGETS = (6, 8, 7, 9)      # none finishes in the first step


def _layout(name):
    """(config, extras) of one cache layout, smoke-sized."""
    if name == "paged":
        return dataclasses.replace(get_config("yi-9b", smoke=True),
                                   cache_layout="paged", kv_page_size=8), {}
    if name == "rings_moe":       # window rings beside the pool, MoE
        return dataclasses.replace(get_config("mellum2-12b", smoke=True),
                                   cache_layout="paged"), {}
    if name == "contiguous":
        return dataclasses.replace(get_config("yi-9b", smoke=True),
                                   cache_layout="contiguous"), {}
    if name == "recurrent":
        return get_config("mamba2-1.3b", smoke=True), {}
    cfg = get_config("whisper-small", smoke=True)     # cross-attention
    # One row of encoder frames in every slot, so a request's tokens do not
    # depend on the slot that admits it.
    row = np.asarray(jax.random.normal(
        jax.random.PRNGKey(4), (1, cfg.enc_seq, cfg.d_model)))
    return cfg, {"frames": np.tile(row, (len(LENS), 1, 1))}


def _requests(cfg, lens=LENS, budgets=BUDGETS, seed=1):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab, n).astype(np.int32),
                    max_new_tokens=m)
            for n, m in zip(lens, budgets)]


def _engine(cfg, extras, **kw):
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return ServeEngine(cfg, params, batch_slots=len(LENS), max_len=MAX_LEN,
                       chunk_size=4, extras=extras, **kw)


@pytest.mark.parametrize("layout", ["paged", "rings_moe", "contiguous",
                                    "recurrent", "cross"])
def test_wave_gives_each_request_its_tokens_alone(layout):
    """One engine serves each request alone (a single-request wave per
    bucket, always into slot 0), then all of them in one wave (slots 0-3):
    the same tokens, no new program, and the wave's counters add exactly
    its requests' pad buckets."""
    cfg, extras = _layout(layout)
    eng = _engine(cfg, extras)
    alone = _requests(cfg)
    for r in alone:
        eng.run([r])
    programs = (eng._prefill._cache_size(), eng._decode_chunk._cache_size())
    before = dict(eng.stats)
    wave = _requests(cfg)
    eng.submit(wave)
    eng.step()
    assert [r.slot for r in wave] == [0, 1, 2, 3]
    eng.drain()
    assert eng.stats["admission_waves"] == before["admission_waves"] + 1
    assert [r.generated for r in wave] == [r.generated for r in alone]
    assert (eng._prefill._cache_size(),
            eng._decode_chunk._cache_size()) == programs
    rows = sum(_pad_bucket(n, MAX_LEN) for n in LENS)
    grew = {k: eng.stats[k] - before[k] for k in before}
    assert grew["prefill_rows_computed"] == rows
    assert grew["prefill_work_tokens"] == sum(LENS)
    if cfg.family == "moe":
        chunk_rows = grew["chunks"] * eng.chunk_size * eng.slots
        assert grew["moe_rows_computed"] == (
            (rows + chunk_rows) * cfg.experts_held * cfg.n_layers)


def test_sharer_in_the_owners_wave_reads_its_fresh_pages():
    """An owner and a request attached to its first two pages, admitted in
    one wave: the sharer prefills only its suffix, after the owner, and
    emits what it emits with sharing off; its history holds its full
    prompt and first token."""
    page = 8
    cfg = dataclasses.replace(get_config("yi-9b", smoke=True),
                              cache_layout="paged", kv_page_size=page)
    rng = np.random.default_rng(0)
    owner = rng.integers(0, cfg.vocab, 20).astype(np.int32)
    sharer = np.concatenate([owner[:2 * page],
                             rng.integers(0, cfg.vocab, 7)]).astype(np.int32)

    def serve(sharing):
        eng = _engine(dataclasses.replace(cfg, prefix_sharing=sharing), {})
        reqs = [Request(prompt=owner, max_new_tokens=6),
                Request(prompt=sharer, max_new_tokens=9)]
        eng.submit(reqs)
        eng.step()
        return eng, reqs

    eng, (o, s) = serve(True)
    assert eng.stats["admission_waves"] == 1
    assert (o.prefix_tokens, s.prefix_tokens) == (0, 2 * page)
    assert eng.stats["prefill_rows_computed"] == (
        _pad_bucket(20, MAX_LEN) + _pad_bucket(7, MAX_LEN))
    hist = np.asarray(eng.hist[s.slot])
    np.testing.assert_array_equal(hist[:len(sharer)], sharer)
    assert hist[len(sharer)] == s.generated[0]
    assert int(eng.hist_len[s.slot]) == len(sharer) + 1
    eng.drain()
    plain, want = serve(False)
    plain.drain()
    assert [r.generated for r in (o, s)] == [r.generated for r in want]


@pytest.mark.parametrize("layout", ["paged", "rings_moe"])
def test_preempted_request_restores_inside_a_wave(layout):
    """A request preempted mid-stream is restored in the same wave as two
    fresh ones (prompt + emitted tokens, prefilled alone in its slot): every
    stream equals its uninterrupted run alone."""
    cfg, extras = _layout(layout)
    eng = _engine(cfg, extras)
    alone = _requests(cfg, LENS[:3], (14, 4, 7))
    for r in alone:
        eng.run([r])
    first, *rest = reqs = _requests(cfg, LENS[:3], (14, 4, 7))
    eng.submit([first])
    eng.step()
    eng.step()
    assert 1 < len(first.generated) < first.max_new_tokens
    eng._preempt(first)
    eng.submit(rest)
    waves = eng.stats["admission_waves"]
    eng.step()
    assert eng.stats["admission_waves"] == waves + 1
    assert eng.stats["readmitted"] == 1
    assert all(r.status == "resident" or r.done for r in reqs)
    eng.drain()
    assert [r.generated for r in reqs] == [r.generated for r in alone]
