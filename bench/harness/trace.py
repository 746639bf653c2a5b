"""Device trace: capture with the JAX profiler and reduce to per-program
device time, device busy time, the top device operations and the longest
idle gaps, each gap labelled by the benchmark's host span it fell in.

Busy time is the union of the intervals in which an operation ran on the
device ("XLA Ops" line of each ``/device:TPU:*`` plane); a program's time is
the sum of its executions on the "XLA Modules" line.  Only events between
the two host markers ``bench.trace_open`` and ``bench.trace_close`` count.
"""
from __future__ import annotations

import collections
import dataclasses
import glob

OPEN, CLOSE = "bench.trace_open", "bench.trace_close"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    programs: dict            # module name -> (executions, device seconds)
    top_ops: list             # [(name, seconds)] longest first
    gaps: list                # [(host span, seconds)] longest first
    planes: list              # device plane names seen

    def program(self, kind_of, kind: str) -> tuple[int, float]:
        """(executions, seconds) of the programs ``kind_of`` maps to kind."""
        n, s = 0, 0.0
        for name, (k, secs) in self.programs.items():
            if kind_of(name) == kind:
                n += k
                s += secs
        return n, s


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _span_at(spans, t):
    """Innermost host span containing t (spans sorted by start)."""
    best = None
    for a, b, name in spans:
        if a > t:
            break
        if b >= t and (best is None or a >= best[0]):
            best = (a, b, name)
    return best[2] if best else "outside bench spans"


def reduce_planes(planes) -> TraceSummary:
    """Reduce ``ProfileData.planes`` (or objects shaped like them)."""
    spans, t0, t1 = [], None, None
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            devices.append(plane)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == OPEN:
                    t0 = e.start_ns
                elif e.name == CLOSE:
                    t1 = e.start_ns
                elif e.name.startswith("bench."):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    if t0 is None or t1 is None or not devices:
        raise ValueError("trace lacks the bench markers or a device plane: "
                         f"{[p.name for p in planes]}")
    spans.sort()
    programs = collections.defaultdict(lambda: [0, 0.0])
    ops = collections.Counter()
    busy_total = 0.0
    gap_list = []
    n_dev = 0
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        op_line = next((lines[k] for k in lines if "XLA Ops" in k), None)
        mod_line = next((lines[k] for k in lines if "XLA Modules" in k), None)
        if op_line is None:
            continue
        n_dev += 1
        iv = []
        for e in op_line.events:
            a, b = e.start_ns, e.start_ns + e.duration_ns
            if b <= t0 or a >= t1:
                continue
            a, b = max(a, t0), min(b, t1)
            iv.append((a, b))
            ops[op_name(e.name)] += (b - a) * 1e-9
        merged = _merge(iv)
        busy_total += sum(b - a for a, b in merged) * 1e-9
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gap_list.append(((b - a) * 1e-9, (a + b) / 2))
        if mod_line is not None:
            for e in mod_line.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if b <= t0 or a >= t1:
                    continue
                p = programs[e.name]
                p[0] += 1
                p[1] += (min(b, t1) - max(a, t0)) * 1e-9
    if n_dev == 0:
        raise ValueError("no XLA Ops line on any device plane: "
                         f"{[[ln.name for ln in p.lines] for p in devices]}")
    gap_list.sort(reverse=True)
    return TraceSummary(
        window_s=(t1 - t0) * 1e-9, busy_s=busy_total / n_dev,
        programs={k: tuple(v) for k, v in programs.items()},
        top_ops=ops.most_common(10),
        gaps=[(_span_at(spans, mid), secs) for secs, mid in gap_list[:10]],
        planes=[p.name for p in devices],
    )


def op_name(name: str) -> str:
    """An op's name without the HLO text the profiler may append to it
    (``%while.41 = (s32[], ...) while(...)`` -> ``%while.41``)."""
    return name.split(" = ", 1)[0][:120]


def reduce_dir(log_dir: str) -> TraceSummary:
    from jax.profiler import ProfileData

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_planes(ProfileData.from_file(sorted(paths)[-1]).planes)
