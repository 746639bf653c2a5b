"""The one place that touches the program's API: it maps a configuration
file onto ``repro``'s ``ModelConfig`` and ``ServeEngine``, and reads the
engine's counters back.

A configuration file states the model (published keys) and the deployment
(``deployment``: slots, max_len, page size, pool pages, prefix sharing, the
warm tier).  Everything else - the decode kernel, its splits, the chunk size
- stays at the program's own defaults, so a PR that changes a default is
measured by the benchmark.
"""
from __future__ import annotations

import dataclasses
import os
import sys

# The program under test: src/ of the checkout this benchmark sits in.
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))

# Published key -> ModelConfig field, for the llama-style dense family.
_DENSE_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "head_dim": "head_dim", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
}


def model_config(config: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.models import get_config

    base = get_config(config["program_arch"])
    kw = {field: config[key] for key, field in _DENSE_KEYS.items()
          if key in config}
    dep = config["deployment"]
    kw.update(
        cache_layout="paged", kv_page_size=dep["page_size"],
        prefix_sharing=bool(dep.get("prefix_sharing", False)),
        adaptive=bool(dep.get("adaptive", False)),
        warm_pages=int(dep.get("warm_pages", 0)),
        dtype=config.get("torch_dtype", base.dtype),
    )
    return dataclasses.replace(base, **kw)


def weight_rows(config: dict) -> int:
    """Rows of the embedding table the program expects (its padded
    vocabulary)."""
    return model_config(config).padded_vocab


def check_layout(config: dict, weights) -> None:
    """The benchmark's weights must have exactly the program's shapes."""
    import jax

    from repro.models import build_model

    model = build_model(model_config(config))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), weights)
    want = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want)
    if got != want:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{got} != {want}")


def build_engine(config: dict, weights):
    from repro.serve.engine import ServeEngine

    dep = config["deployment"]
    return ServeEngine(model_config(config), weights,
                       batch_slots=dep["slots"], max_len=dep["max_len"],
                       n_pages=dep["pool_pages"])


def request(prompt, max_new: int, rid: str):
    from repro.serve.engine import Request

    return Request(prompt=prompt, max_new_tokens=max_new, id=rid)


def busy(engine) -> bool:
    """Work queued or resident."""
    return bool(engine.queue) or any(r is not None for r in engine.slot_req)


def release(engine) -> None:
    """Drop the engine's device state (KV pool, slot vectors) so the
    reference can use the memory; the weights stay, they are ours."""
    import jax

    for k, v in list(vars(engine).items()):
        if k != "params" and (k == "cache" or isinstance(v, jax.Array)):
            setattr(engine, k, None)


def counters(engine) -> dict:
    return engine.serve_stats()


def pool_pages(engine) -> int:
    return engine.n_pages


def chunk_steps(engine) -> int:
    """Decode steps per decode-chunk dispatch."""
    return engine.chunk_size


def program_kind(module: str) -> str | None:
    """Which of the engine's jitted programs a device-trace module is:
    the admission prefill or the decode chunk."""
    if "_prefill_fn" in module:
        return "prefill"
    if "chunk_fn" in module:
        return "decode"
    return None
