"""Drive a cell's traffic through the engine's public loop (``submit``,
``step``, ``cancel``) and time every request on the benchmark's own clock.

Tokens reach the caller when ``step()`` returns, so a token's time is the
host clock right after the step that delivered it.  A request's time to
first token runs from when it was due (not when it was submitted: a late
generator or a long step counts against it).
"""
from __future__ import annotations

import dataclasses
import heapq
import time

import numpy as np

from harness import adapter
from harness.traffic import Stream, prompt_range, shares_prefixes

# After the window closes, arrivals stop and the loop runs on until every
# request due inside the window has finished, for at most this long.
DRAIN_LIMIT_S = 60.0

clock = time.perf_counter


@dataclasses.dataclass
class Record:
    rid: str
    due: float                  # absolute host time it was due
    submit: float | None = None
    first: float | None = None  # step return that delivered token 0
    last: float | None = None   # step return that delivered the last token
    n: int = 0
    status: str = "due"
    counted: bool = False       # due inside the measured window
    req: object = None


class Steps:
    """Host-clock step log: each step's return time and what it
    delivered, so the metrics can cut any interval out of it."""

    def __init__(self):
        self.t = []             # return time of each step
        self.tokens = []        # tokens delivered by it
        self.prefills = []      # [(cached prefix, prefilled tokens)]
        self.decodes = []       # [context of each decoded token]


def _buckets(lo: int, hi: int) -> list[int]:
    """The power-of-two buckets (b/2, b] that [lo, hi] reaches."""
    out, b = [], 8
    while b // 2 < hi:
        if b >= lo:
            out.append(b)
        b *= 2
    return out


def warm_up(engine, traffic: dict, config: dict, rng) -> int:
    """Compile every shape the traffic reaches, through the public loop:
    one prefill per prompt-length bucket and, where prompts share
    prefixes, one per (suffix bucket, prompt bucket) pair of a request
    attached to a resident prefix; the decode chunk with them.  Returns
    the steps run."""
    dep = config["deployment"]
    vocab, psz, cap = config["vocab_size"], dep["page_size"], dep["max_len"]
    lo, hi = prompt_range(traffic)
    steps = [0]
    live: list = []

    def tokens(n):
        return rng.integers(0, vocab, n, dtype=np.int32)

    def run(prompt, budget=2):
        r = adapter.request(prompt, budget, f"warm-{steps[0]}")
        for old in live:
            engine.cancel(old.id)
        live[:] = [r]
        engine.submit([r])
        engine.step()
        steps[0] += 1

    for b in _buckets(lo, hi):
        run(tokens(min(b, hi)))
    if shares_prefixes(traffic) and dep.get("prefix_sharing"):
        for b in _buckets(lo, hi):
            shared = max(psz, b // 2 // psz * psz)
            base = tokens(shared)
            holder = adapter.request(base, min(256, cap - shared),
                                     f"warm-base-{b}")
            engine.submit([holder])
            engine.step()
            p = 8
            while p <= b // 2:
                run(np.concatenate([base, tokens(min(p, cap - 2 - shared))]))
                p *= 2
            engine.cancel(holder.id)
    for old in live:
        engine.cancel(old.id)
    while adapter.busy(engine):
        engine.step()
        steps[0] += 1
    return steps[0]


def ramp(engine, streams: list[Stream], recs: dict, now) -> None:
    """Closed loop: every client's first request, until every one is
    resident (part of set-up)."""
    t = now()
    reqs = []
    for s in streams:
        rec = Record(rid=f"{s.sid}.0", due=t)
        rec.req = adapter.request(s.prompt(0, []), s.turns[0].max_new,
                                  rec.rid)
        rec.submit = t
        recs[rec.rid] = rec
        reqs.append(rec.req)
    engine.submit(reqs)
    while engine.queue:
        engine.step()


def drive(engine, streams: list[Stream], seconds: float, closed: bool,
          span, on_step=None, recs=None):
    """Run the window.  Returns (records, steps, t_open, t_close).

    ``span(name)`` is a context manager around each host phase (the
    profiler's host annotations in a traced run).  ``on_step(t, t_end)`` is
    called after every step (the tracer starts and stops there)."""
    recs = {} if recs is None else recs
    answers = {s.sid: [] for s in streams}
    by_sid = {s.sid: s for s in streams}
    steps = Steps()
    t_open = clock()
    t_end = t_open + seconds
    heap = []          # (due, sid, turn), all due before t_end
    inflight: dict[str, None] = {}
    if closed:
        for rec in recs.values():     # the ramp's requests
            rec.counted = True
            rec.n = len(rec.req.generated)
            rec.first = rec.submit
            inflight[rec.rid] = None
    else:
        for s in streams:
            if t_open + s.arrival_s < t_end:
                heapq.heappush(heap, (t_open + s.arrival_s, s.sid, 0))

    def submit_due(now):
        if not heap or heap[0][0] > now:
            return
        with span("bench.generate"):
            batch = []
            while heap and heap[0][0] <= now:
                due, sid, j = heapq.heappop(heap)
                s = by_sid[sid]
                rec = Record(rid=f"{sid}.{j}", due=due, counted=True)
                rec.req = adapter.request(s.prompt(j, answers[sid]),
                                          s.turns[j].max_new, rec.rid)
                recs[rec.rid] = rec
                batch.append(rec)
        with span("bench.submit"):
            t = clock()
            for rec in batch:
                rec.submit = t
                try:
                    engine.submit([rec.req])
                except ValueError as e:       # refused at admission
                    rec.status = f"rejected: {e}"
                    continue
                inflight[rec.rid] = None

    def harvest(t):
        pre, dec, got, done = [], [], 0, []
        for rid in inflight:
            rec = recs[rid]
            r = rec.req
            n = len(r.generated)
            if n > rec.n:
                base = len(r.prompt)
                if rec.n == 0:
                    rec.first = t
                    pre.append((r.prefix_tokens, base - r.prefix_tokens))
                    dec += [base + i for i in range(1, n)]
                else:
                    dec += [base + i for i in range(rec.n, n)]
                got += n - rec.n
                rec.n = n
            if r.done:
                rec.last = t
                rec.status = r.status
                done.append(rid)
        steps.t.append(t)
        steps.tokens.append(got)
        steps.prefills.append(pre)
        steps.decodes.append(dec)
        for rid in done:
            del inflight[rid]
            sid, j = (int(x) for x in rid.split("."))
            s = by_sid[sid]
            answers[sid].append(list(recs[rid].req.generated))
            if recs[rid].status == "finished" and j + 1 < len(s.turns):
                due = t + s.turns[j].think_s
                if closed or due < t_end:
                    heapq.heappush(heap, (due, sid, j + 1))

    t_close = None
    while True:
        now = clock()
        submit_due(now)
        if inflight:
            with span("bench.step"):
                engine.step()
            t = clock()
            with span("bench.harvest"):
                harvest(t)
            if on_step is not None:
                on_step(t, t_end)
            if t_close is None and t >= t_end:
                t_close = t
        elif now < t_end:
            wait = heap[0][0] - now if heap else t_end - now
            time.sleep(max(0.0, min(wait, 0.01)))
            continue
        elif t_close is None:
            t_close = now
        if t_close is not None:
            if closed or not (inflight or heap):
                break
            if clock() - t_close > DRAIN_LIMIT_S:
                break
    return recs, steps, t_open, t_close
