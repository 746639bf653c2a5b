"""The comparison that decides ``correct``: served greedy tokens against the
benchmark's own float32 reference (``models/<model>.py``).

After the window, a sample of the finished requests drawn from the seed -
the longest request among them, then others until ``SAMPLE_TOKENS`` served
tokens - is run through the reference once each, over prompt + served
tokens.  Every served token's logit must lie within ``limit`` of the
reference's largest logit at its position.  A token outside the vocabulary,
or a stream shorter than its budget, fails outright.
"""
from __future__ import annotations

import dataclasses

import numpy as np

SAMPLE_TOKENS = 512
SAMPLE_MAX = 8
_CHECK_SALT = 0xC4EC


@dataclasses.dataclass
class CheckResult:
    worst_gap: float
    tokens: int
    requests: int
    bad_streams: int        # out-of-vocabulary token or short stream

    def ok(self, limit: float) -> bool:
        return (self.tokens > 0 and self.bad_streams == 0
                and self.worst_gap <= limit)


def sample(finished: list, seed: int) -> list:
    """The longest finished request, then a seeded draw of the others until
    SAMPLE_TOKENS served tokens or SAMPLE_MAX requests."""
    if not finished:
        return []
    rng = np.random.default_rng([seed % (2 ** 64), _CHECK_SALT])
    order = sorted(finished, key=lambda r: r.rid)
    longest = max(order, key=lambda r: len(r.req.prompt) + len(r.req.generated))
    rest = [order[i] for i in rng.permutation(len(order)) if order[i] is not longest]
    picked, tokens = [longest], len(longest.req.generated)
    for r in rest:
        if tokens >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX:
            break
        picked.append(r)
        tokens += len(r.req.generated)
    return picked


def width(n: int, cap: int) -> int:
    w = 256
    while w < n:
        w *= 2
    return min(w, cap)


def stream_inputs(prompt, generated, cap: int):
    """(tokens (W,), targets (W,), rows) for one served stream: the
    reference runs over prompt + generated[:-1]; the token served after
    position i is targets[i]."""
    prompt = np.asarray(prompt, np.int32)
    gen = np.asarray(generated, np.int32)
    seq = np.concatenate([prompt, gen[:-1]])
    W = width(len(seq), cap)
    tokens = np.zeros(W, np.int32)
    tokens[:len(seq)] = seq
    rows = len(prompt) - 1 + np.arange(len(gen))
    targets = np.zeros(W, np.int32)
    targets[rows] = gen
    return tokens, targets, rows


def run_check(gap_fn, weights, picked: list, vocab: int, cap: int,
              gaps_of=lambda out: out) -> CheckResult:
    import jax

    worst, tokens, bad = 0.0, 0, 0
    for rec in picked:
        r = rec.req
        gen = np.asarray(r.generated)
        if len(gen) != r.max_new_tokens or (gen >= vocab).any() or (gen < 0).any():
            bad += 1
            continue
        tok, tgt, rows = stream_inputs(r.prompt, gen, cap)
        gaps = np.asarray(gaps_of(jax.device_get(gap_fn(weights, tok, tgt))))
        worst = max(worst, float(gaps[rows].max()))
        tokens += len(rows)
    return CheckResult(worst_gap=worst, tokens=tokens, requests=len(picked),
                       bad_streams=bad)
