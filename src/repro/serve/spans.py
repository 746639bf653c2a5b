"""Host spans of the serving loop, written into the JAX profiler's trace.

Each span is a ``jax.profiler.TraceAnnotation``: while a trace is active
the profiler records it on the same clock as the device's operations, so a
device idle gap can be put down to the engine phase the host was in.  With
no trace active a span costs the construction of that object and nothing
else.  ``NAMES`` is the only place the names are spelled.
"""
from __future__ import annotations

import jax

NAMES = (
    "serve.step",           # the whole of ServeEngine.step()
    "serve.sweep",          # lifecycle sweep (cancel / expire)
    "serve.admit",          # admission bookkeeping up to the prefill
    "serve.admit.request",  # one admitted request (id, prefix, suffix)
    "serve.preempt",        # one eviction (id of the victim)
    "serve.prefill",        # prefill arguments and dispatch (rows)
    "serve.prefill.sync",   # host waiting for the wave's first tokens
    "serve.replan",         # adaptive policy re-plan
    "serve.decode",         # decode-chunk dispatch
    "serve.decode.sync",    # host waiting for the chunk's tokens
    "serve.emit",           # tokens handed to requests, finished retired
    "serve.integrity",      # KV page integrity sweep
    "serve.journal",        # request journal flush
)


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """The span ``name`` (one of ``NAMES``) with its metadata."""
    if name not in NAMES:
        raise ValueError(f"unknown span {name!r}")
    return jax.profiler.TraceAnnotation(name, **meta)
