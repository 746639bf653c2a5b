"""The teacher-forced stream check that ``chip_smoke.py`` applies at full
width, exercised at smoke size: it accepts what ServeEngine serves on
either decode path and rejects a stream with one token changed."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.models import build_model, get_config
from repro.serve.engine import Request, ServeEngine
from repro.serve.verify import check_stream, make_gap_fn

MARGIN_ULP = 8.0
NEW_TOKENS = 12


@pytest.fixture(scope="module")
def served():
    """minicpm-2b (smoke) weights and ragged prompts, served greedily on a
    paged pool by each decode path."""
    cfg = dataclasses.replace(get_config("minicpm-2b", smoke=True),
                              cache_layout="paged", kv_page_size=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n, dtype=np.int32)
               for n in (5, 17, 30, 9)]
    out = {}
    for kernel in ("xla", "pallas_paged"):
        engine = ServeEngine(dataclasses.replace(cfg, decode_kernel=kernel),
                             params, batch_slots=4, max_len=64, chunk_size=4)
        reqs = [Request(prompt=p, max_new_tokens=NEW_TOKENS) for p in prompts]
        engine.run(reqs)
        out[kernel] = reqs
    return model, params, cfg, out


def _check(model, params, prompt, generated):
    return check_stream(make_gap_fn(model), params, prompt, generated,
                        width=48, margin_ulp=MARGIN_ULP)


@pytest.mark.parametrize("kernel", ["xla", "pallas_paged"])
def test_check_accepts_served_streams(served, kernel):
    model, params, _, out = served
    for r in out[kernel]:
        res = _check(model, params, r.prompt, r.generated)
        assert res.ok, res.gaps_ulp
        assert len(r.generated) == NEW_TOKENS


def test_check_rejects_one_perturbed_token(served):
    model, params, cfg, out = served
    r = out["xla"][1]
    bad = list(r.generated)
    j = NEW_TOKENS // 2
    bad[j] = (bad[j] + cfg.vocab // 2) % cfg.vocab
    res = _check(model, params, r.prompt, bad)
    assert not res.ok
    assert res.gaps_ulp[j] > MARGIN_ULP
    assert np.all(res.gaps_ulp[:j] <= MARGIN_ULP)
