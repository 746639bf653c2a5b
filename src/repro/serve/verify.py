"""Teacher-forced check of served greedy streams against a cache-free forward.

The engine's prefill and cached decode must produce, at every generated
position, the token a plain ``model.forward`` over the same sequence would
pick.  One forward runs over ``prompt + generated`` (the engine's own
stream, so a near-tie never compounds into a different continuation), and
each generated token's logit is compared with the row's maximum.

In a reduced precision two paths that order their sums differently round
differently, so a token may miss the exact argmax by a near-tie.  The gap
is therefore measured in units of the bf16 spacing at the row's largest
logit (``ulp``): a correct path stays within a few units, while a wrong
token sits a large fraction of the logit range below the maximum.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# bf16 keeps 8 significant bits: the spacing of values in [2^e, 2^(e+1))
# is 2^(e-7).
_BF16_MANTISSA_BITS = 7


@dataclasses.dataclass(frozen=True)
class StreamCheck:
    """Per-stream result: ``gaps_ulp[j]`` is how far generated token j sits
    below the reference row maximum, in bf16 spacings of that maximum."""

    gaps_ulp: np.ndarray
    margin_ulp: float

    @property
    def ok(self) -> bool:
        return bool(np.all(self.gaps_ulp <= self.margin_ulp))

    @property
    def exact(self) -> int:
        """Positions whose token is the reference argmax (gap 0)."""
        return int(np.sum(self.gaps_ulp == 0))


def make_gap_fn(model):
    """Jitted (params, tokens (1, W), pos (n,), targets (n,)) -> (gap, max)
    over one cache-free forward; fixed W and n mean one compilation."""

    @jax.jit
    def gaps(params, tokens, pos, targets):
        logits, _ = model.forward(params, tokens)
        rows = logits[0, pos].astype(jnp.float32)                 # (n, V)
        picked = jnp.take_along_axis(rows, targets[:, None], axis=1)[:, 0]
        top = jnp.max(rows, axis=-1)
        return top - picked, top

    return gaps


def check_stream(gap_fn, params, prompt, generated, *, width: int,
                 margin_ulp: float) -> StreamCheck:
    """Check one served stream; ``width`` pads every forward to one shape
    (causal attention: padding after the stream changes no row checked)."""
    prompt = np.asarray(prompt, np.int32)
    gen = np.asarray(generated, np.int32)
    n = len(gen)
    seq = np.concatenate([prompt, gen[:-1]])
    if len(seq) > width:
        raise ValueError(f"stream of {len(seq)} tokens exceeds width {width}")
    tokens = np.zeros((1, width), np.int32)
    tokens[0, :len(seq)] = seq
    pos = len(prompt) - 1 + np.arange(n, dtype=np.int32)
    gap, top = jax.device_get(gap_fn(params, tokens, pos, gen))
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(top), 1e-30)))
                  - _BF16_MANTISSA_BITS)
    return StreamCheck(gaps_ulp=np.asarray(gap / ulp), margin_ulp=margin_ulp)
