"""One general generator for every traffic mix: a mix is a JSON file of
parameters, and this module turns it and ``--seed`` into streams of turns.

A *stream* is a sequence of turns sent one after another: a chat session
(system prompt + growing history), a closed-loop client (one request after
another), or a single independent request (a stream of one turn).  Turn 0
of a stream is due at the stream's arrival; turn j+1 is due when turn j's
last token has come back, plus a think time.

Every seed gets the same work: the streams' sizes (turns, prompt and answer
lengths, think times), their arrival times and their system prompts are
drawn once from a fixed generator, whatever the seed.  The seed draws every
token id and, in a closed loop, where every client starts at once, which
client runs which stream: the same streams in another order.  An open loop
keeps its streams at their arrival times, because which stream arrives when
decides how much of the work falls inside the window.

Parameters (see ``traffic/*.json``):

* ``arrivals``: ``{"process": "gamma", "cv": c, "rate_per_s": r}`` for an
  open loop (``cv`` 1 is Poisson), or ``{"process": "closed", "clients": n}``
  (every client's first turn is due at once).
* ``system``: ``{"count": k, "tokens": n}`` - each stream opens with one of
  k shared system prompts of n tokens (optional).
* ``turns``: ``{"min": a, "max": b}`` turns per stream (default 1).
* ``history``: a turn's prompt carries the stream's earlier turns and
  answers (chat) or not.
* ``prompt``, ``output``: length distributions of the new user text and of
  the answer, ``{"dist": "lognormal", "median", "sigma", "min", "max"}``.
* ``think_s``: ``{"dist": "exponential", "mean": s}`` between turns.
* ``max_prompt``: a stream ends before a turn whose prompt would exceed it.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

# The fixed generator that draws every seed's multiset of sizes.
_SIZES_SEED = 0x51235


def _draw(spec: dict, n: int, tag: int) -> np.ndarray:
    rng = np.random.default_rng([_SIZES_SEED, tag])
    dist = spec["dist"]
    if dist == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    elif dist == "exponential":
        x = rng.exponential(spec["mean"], n)
    elif dist == "constant":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "min" in spec or "max" in spec:
        x = np.clip(x, spec.get("min", -np.inf), spec.get("max", np.inf))
    return x


def _gaps(arr: dict, n: int) -> np.ndarray:
    """Inter-arrival gaps with mean 1/rate and coefficient of variation cv
    (gamma with shape 1/cv^2; cv 1 is exponential, i.e. Poisson)."""
    rng = np.random.default_rng([_SIZES_SEED, 1])
    cv, mean = arr.get("cv", 1.0), 1.0 / arr["rate_per_s"]
    shape = 1.0 / (cv * cv)
    return rng.gamma(shape, mean / shape, n)


@dataclasses.dataclass
class Turn:
    user: np.ndarray        # new user tokens of this turn
    max_new: int            # answer length (greedy, no stop token)
    think_s: float          # pause after this turn's answer


@dataclasses.dataclass
class Stream:
    sid: int
    arrival_s: float        # due time of turn 0, from the window's opening
    system: np.ndarray      # shared system prompt (may be empty)
    turns: list
    history: bool

    def prompt(self, j: int, answers: list) -> np.ndarray:
        """Turn j's prompt given the answers served for turns < j."""
        parts = [self.system]
        if self.history:
            for k in range(j):
                parts += [self.turns[k].user, np.asarray(answers[k], np.int32)]
        parts.append(self.turns[j].user)
        return np.concatenate(parts).astype(np.int32)


def _prompt_lens(sys_len: int, users: list, outs: list, history: bool):
    """Prompt length of each turn before any answer is served: answers are
    exactly ``max_new`` tokens, so the lengths are known up front."""
    lens, hist = [], 0
    for u, o in zip(users, outs):
        lens.append(sys_len + hist + u)
        if history:
            hist += u + o
    return lens


def generate(traffic: dict, seed: int, seconds: float, vocab: int,
             rate: float | None = None) -> list[Stream]:
    """Streams for one run.  An open loop gets enough arrivals to cover
    ``seconds``; ``rate`` overrides the mix's rate (for knee sweeps)."""
    arr = dict(traffic["arrivals"])
    if rate is not None:
        arr["rate_per_s"] = rate
    closed = arr["process"] == "closed"
    n = (arr["clients"] if closed
         else int(math.ceil(arr["rate_per_s"] * seconds * 1.25)) + 8)
    tmin = traffic.get("turns", {}).get("min", 1)
    tmax = traffic.get("turns", {}).get("max", tmin)
    fixed = np.random.default_rng([_SIZES_SEED, 5])
    # Seed-free: every stream's sizes, arrival and system prompt.
    n_turns = fixed.permutation(np.resize(np.arange(tmin, tmax + 1), n))
    total = int(n_turns.sum())
    users = np.rint(_draw(traffic["prompt"], total, 2))
    outs = np.rint(_draw(traffic["output"], total, 3))
    think = (_draw(traffic["think_s"], total, 4)
             if "think_s" in traffic else np.zeros(total))
    if closed:
        arrivals = np.zeros(n)
    else:
        arrivals = np.cumsum(_gaps(arr, n))
        arrivals -= arrivals[0]     # the first stream opens the window
    sysc = traffic.get("system", {"count": 0, "tokens": 0})
    sys_pick = np.arange(n) % max(1, sysc["count"])
    # Seeded: token ids, and which client runs which stream.
    rng = np.random.default_rng(seed % (2 ** 64))
    systems = [rng.integers(0, vocab, sysc["tokens"], dtype=np.int32)
               for _ in range(sysc["count"])]
    client = rng.permutation(n) if closed else np.arange(n)
    history = bool(traffic.get("history", False))
    max_prompt = traffic.get("max_prompt", 1 << 30)
    starts = np.concatenate([[0], np.cumsum(n_turns)])
    streams = []
    for c in range(n):
        i = int(client[c])
        at, k = int(starts[i]), int(n_turns[i])
        us = [int(x) for x in users[at:at + k]]
        os_ = [int(x) for x in outs[at:at + k]]
        ths = [float(x) for x in think[at:at + k]]
        system = (systems[sys_pick[i]] if systems
                  else np.zeros(0, np.int32))
        lens = _prompt_lens(len(system), us, os_, history)
        keep = next((j for j, L in enumerate(lens) if L > max_prompt), k)
        turns = [Turn(user=rng.integers(0, vocab, us[j], dtype=np.int32),
                      max_new=os_[j], think_s=ths[j]) for j in range(keep)]
        if turns:
            streams.append(Stream(sid=c, arrival_s=float(arrivals[i]),
                                  system=system, turns=turns,
                                  history=history))
    return streams


def prompt_range(traffic: dict) -> tuple[int, int]:
    """(shortest, longest) prompt a mix can send."""
    sys_len = traffic.get("system", {}).get("tokens", 0)
    lo = sys_len + traffic["prompt"].get("min", 1)
    hi = sys_len + traffic["prompt"].get("max", lo)
    if traffic.get("history"):
        hi = max(hi, traffic.get("max_prompt", hi))
    return lo, min(hi, traffic.get("max_prompt", hi))


def shares_prefixes(traffic: dict) -> bool:
    return bool(traffic.get("system")) or bool(traffic.get("history"))
