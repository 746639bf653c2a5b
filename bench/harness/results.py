"""What a run leaves for the metric readers (``metrics/<name>.py``), and the
arithmetic they share.  A reader returns a number, or None where the run
has nothing for it to read (the metric is then left out of the line)."""
from __future__ import annotations

import dataclasses

import numpy as np


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile over every value (numpy's default)."""
    v = np.asarray(list(values), float)
    return float(np.percentile(v, q)) if v.size else None


@dataclasses.dataclass
class RunData:
    records: list            # requests due in the window (driver.Record)
    steps: object            # driver.Steps
    t_open: float
    t_close: float
    t_last: float            # end of the drain
    setup_s: float
    counters_open: dict      # engine counters at the window's edges
    counters_close: dict
    pool_pages: int
    chunk_steps: int
    closed: bool
    config: dict
    model: object            # models/<model>.py
    peak_flops: float
    kind_of: object          # device-trace module name -> program kind
    trace: object = None     # trace.TraceSummary of the traced run
    trace_t: tuple = None    # host times of the traced interval

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def counter(self, key: str) -> float:
        return self.counters_close[key] - self.counters_open[key]

    def step_range(self, t0: float, t1: float) -> list[int]:
        return [i for i, t in enumerate(self.steps.t) if t0 < t <= t1]

    def window_steps(self) -> list[int]:
        return self.step_range(self.t_open, self.t_close)

    def traced_steps(self) -> list[int]:
        return self.step_range(*self.trace_t) if self.trace_t else []

    def ttfts(self) -> list[float]:
        """Due to first token, every request due in the window; one that
        never got a first token counts with the time it waited."""
        return [(r.first if r.first is not None else self.t_last) - r.due
                for r in self.records]

    def tpots(self) -> list[float]:
        return [(r.last - r.first) / (r.n - 1) for r in self.records
                if r.last is not None and r.first is not None and r.n > 1]

    def program(self, kind: str) -> tuple[int, float]:
        """(executions, device seconds) of one engine program in the
        trace, or (0, 0.0) without one."""
        if self.trace is None:
            return 0, 0.0
        return self.trace.program(self.kind_of, kind)

    def prefill_flops(self) -> float:
        f = self.model.prefill_flops
        return sum(f(self.config, a, n) for i in self.traced_steps()
                   for a, n in self.steps.prefills[i])

    def decode_flops(self) -> float:
        f = self.model.decode_flops
        return sum(f(self.config, c) for i in self.traced_steps()
                   for c in self.steps.decodes[i])


def decode_step_ms(run: RunData) -> float | None:
    """Device time of the decode-chunk program per decode step."""
    n, s = run.program("decode")
    return 1000.0 * s / (n * run.chunk_steps) if n else None


def decode_mfu(run: RunData) -> float | None:
    """Useful decode FLOPs of the traced steps over the decode program's
    device time at the chip's peak."""
    _, s = run.program("decode")
    if not s:
        return None
    flops = run.decode_flops()
    return 100.0 * flops / (s * run.peak_flops) if flops else None


def idle_share(run: RunData) -> float | None:
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
