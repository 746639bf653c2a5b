"""One run of one cell: build the engine from the configuration with weights
made on the device from ``--seed``, compile and warm every shape the
cell's traffic reaches, drive the traffic for ``--seconds``, check the
served streams against the reference, and print the result line.

Refuses to run (exit 3, no result) without a TPU or with fewer chips than
the cell asks for: a number from any other device is not this benchmark's.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import time

from harness import spec

clock = time.perf_counter
# Seconds of the window, at its end, that a --trace 1 run records.
TRACE_S = 10.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def peaks_for(kind: str) -> dict:
    with open(os.path.join(spec.BENCH, "peaks.json")) as f:
        table = json.load(f)["chips"]
    if kind not in table:
        raise LookupError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def require_chips(jax, chips: int):
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"needs a TPU, JAX found platform {dev.platform!r} "
                           f"({dev.device_kind})")
    if len(devices) < chips:
        raise RuntimeError(f"cell needs {chips} chips, JAX found "
                           f"{len(devices)}")
    return devices


@contextlib.contextmanager
def no_span(_name):
    yield


def step_summary(steps, t_open, t_close, c_open, c_close) -> str:
    """The window's steps on the host clock: how many, how many admitted a
    wave, the tokens they delivered, the longest intervals between step
    returns (a host stall shows as one far above the rest), and the
    engine's preemptions in the window."""
    import numpy as np

    idx = [i for i, t in enumerate(steps.t) if t_open < t <= t_close]
    t = np.array([t_open] + [steps.t[i] for i in idx])
    d = np.diff(t)
    waves = [bool(steps.prefills[i]) for i in idx]
    top = sorted(zip(d.tolist(), waves), reverse=True)[:3]
    dec = d[[not w for w in waves]] if len(d) else d
    return (f"[steps] {len(idx)} steps, {sum(waves)} with a prefill wave, "
            f"{sum(steps.tokens[i] for i in idx)} tokens; interval median "
            f"{np.median(d) if len(d) else 0:.4f}s, decode-only median "
            f"{np.median(dec) if len(dec) else 0:.4f}s max "
            f"{dec.max() if len(dec) else 0:.4f}s; longest "
            + ", ".join(f"{x:.4f}s{' (wave)' if w else ''}" for x, w in top)
            + f"; preempted {c_close.get('preempted', 0) - c_open.get('preempted', 0)}")


def main(argv=None, t_start=None) -> int:
    t_start = clock() if t_start is None else t_start
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    import jax

    try:
        devices = require_chips(jax, cell.chips)
        peaks = peaks_for(devices[0].device_kind)
    except (RuntimeError, LookupError) as e:
        log(f"bench: {e}")
        return 3
    result = run_cell(cell, args, devices[:cell.chips], peaks, t_start)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(cell, args, dev, peaks, t_start) -> dict:
    import jax
    import numpy as np

    from harness import adapter, check, compiles, driver, results, traffic
    from harness import trace as tr

    cache = compiles.use_cache(jax, spec.ROOT)
    clog = compiles.CompileLog(jax)
    config = cell.config
    model = spec.model_module(config)
    readers = {m["name"]: spec.metric_reader(m["name"])
               for m in (cell.per_layer if args.trace else cell.end_to_end)}
    if not args.trace:
        readers.setdefault("setup_s", spec.metric_reader("setup_s"))

    weights = model.init_weights(config, args.seed, adapter.weight_rows(config))
    jax.block_until_ready(weights)
    adapter.check_layout(config, weights)
    engine = adapter.build_engine(config, weights)
    rng = np.random.default_rng([args.seed % (2 ** 64), 0x3A53])
    warm_steps = driver.warm_up(engine, cell.traffic, config, rng)
    streams = traffic.generate(cell.traffic, args.seed, args.seconds,
                               config["vocab_size"])
    closed = cell.traffic["arrivals"]["process"] == "closed"
    recs = {}
    if closed:
        driver.ramp(engine, streams, recs, clock)
    counters_open = adapter.counters(engine)
    setup_mark = clog.mark()
    log(f"[setup] {cell.name}: {time.perf_counter() - t_start:.3f}s "
        f"({warm_steps} warm-up steps), compile cache {cache}, "
        f"{clog.summary()}")

    trace_dir = os.path.join(spec.ROOT, ".bench_trace", cell.name)
    tracing = {"on": False, "t": None, "t0": None}
    span = no_span
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = jax.profiler.TraceAnnotation
        # Device activity and the benchmark's own host spans; no Python
        # function tracing, which would slow the host it is measuring.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
    counters_close = {}

    def on_step(t, t_end):
        if args.trace and not tracing["on"] and tracing["t"] is None \
                and t >= t_end - TRACE_S:
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(tr.OPEN):
                pass
            tracing.update(on=True, t0=clock())
        if t >= t_end and not counters_close:
            counters_close.update(adapter.counters(engine))
            if tracing["on"]:
                with jax.profiler.TraceAnnotation(tr.CLOSE):
                    pass
                tracing["t"] = (tracing["t0"], t)
        if closed and tracing["on"] and counters_close:
            jax.profiler.stop_trace()
            tracing["on"] = False

    recs, steps, t_open, t_close = driver.drive(
        engine, streams, args.seconds, closed, span, on_step, recs)
    t_last = clock()
    if not counters_close:
        counters_close.update(adapter.counters(engine))
    if tracing["on"]:
        if tracing["t"] is None:
            with jax.profiler.TraceAnnotation(tr.CLOSE):
                pass
            tracing["t"] = (tracing["t0"], t_close)
        jax.profiler.stop_trace()
    window = clog.since(setup_mark)
    stats = dev[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in dev)
    counted = [r for r in recs.values() if r.counted]
    late = [r.submit - r.due for r in counted if r.submit is not None]
    log(f"[window] {t_close - t_open:.3f}s, {len(counted)} requests, "
        f"drain {t_last - t_close:.3f}s; compiles in window "
        f"{window['compiles']} (traces {window['traces']}) "
        f"{window['names']}; {clog.summary()}; bytes_in_use "
        f"{stats.get('bytes_in_use')} peak_bytes_in_use {peak}; "
        f"generator lateness mean {np.mean(late) if late else 0:.6f}s "
        f"max {max(late) if late else 0:.6f}s")
    log(step_summary(steps, t_open, t_close, counters_open, counters_close))
    pool = adapter.pool_pages(engine)
    steps_per_chunk = adapter.chunk_steps(engine)
    summary = None
    if args.trace:
        summary = tr.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # The program's state goes before the reference runs.
    finished = [r for r in recs.values() if r.status == "finished"]
    adapter.release(engine)
    del engine
    gc.collect()
    picked = check.sample(finished, args.seed)
    gap_fn = model.make_gap_fn(config)
    res = check.run_check(gap_fn, weights, picked, config["vocab_size"],
                          config["deployment"]["max_len"])
    limit = config["check"]["max_logit_gap"]

    run = results.RunData(
        records=counted, steps=steps, t_open=t_open, t_close=t_close,
        t_last=t_last, setup_s=t_open - t_start,
        counters_open=counters_open, counters_close=counters_close,
        pool_pages=pool, chunk_steps=steps_per_chunk, closed=closed,
        config=config, model=model, peak_flops=peaks["bf16_flops"],
        kind_of=adapter.program_kind, trace=summary,
        trace_t=tracing["t"],
    )
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    units.setdefault("setup_s", "s")
    metrics = {}
    for name, read in readers.items():
        v = read(run)
        if v is not None:
            metrics[name] = {"value": v, "unit": units[name]}
    # A closed loop's requests still running when the window closes are cut
    # by the window, not failed; an open loop's had the drain to finish.
    ok_status = ("finished", "due") if closed else ("finished",)
    failed = sum(1 for r in counted if r.status not in ok_status)
    checks = {
        "worst_logit_gap": {"value": res.worst_gap, "limit": limit},
        "bad_streams": {"value": res.bad_streams, "limit": 0},
        "checked_tokens_at_least": {"value": res.tokens, "limit": 1},
    }
    out = {
        "correct": res.ok(limit),
        "attempted": len(counted),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev[0].platform, "kind": dev[0].device_kind,
                   "count": len(dev), "memory_peak_bytes": peak},
    }
    if summary is not None:
        out["device"]["busy_s"] = summary.busy_s
        out["device"]["window_s"] = summary.window_s
        progs = sorted(summary.programs.items(), key=lambda kv: -kv[1][1])
        out["breakdown"] = {
            "device_ops": ([[f"program {k}", v[1]] for k, v in progs[:3]]
                           + [[k, v] for k, v in summary.top_ops])[:10],
            "idle_gaps": [[k, v] for k, v in summary.gaps][:10],
        }
    out["checks"] = checks
    log(f"[check] {res.requests} requests, {res.tokens} tokens compared")
    for k, v in checks.items():
        log(f"[check] {k} {v['value']} limit {v['limit']}")
    return out
