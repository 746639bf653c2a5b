#!/usr/bin/env python3
"""Knee sweep for an open-loop cell on the chip:

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 0.5 1 2 ...

One process builds and warms the cell's engine once, then serves the cell's
traffic for ``--seconds`` at each rate in turn (each window drained before
the next) and prints, per rate, what says whether the engine sustains it:
requests due and finished, time to first token at the median and 90th
percentile, the median over the last third of the window against the first
third (a queue that grows through the window pushes it up), and how long
the drain after the window took.  The cell's rate is then fixed in its
traffic file at about four fifths of the highest rate sustained.  The
benchmark's own runs do not run this.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SID_STRIDE = 1_000_000


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    from harness import adapter, cli, compiles, driver, results, spec, traffic

    cell = spec.load_cell(args.workload)
    try:
        cli.require_chips(jax, cell.chips)
    except RuntimeError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    compiles.use_cache(jax, spec.ROOT)
    config = cell.config
    model = spec.model_module(config)
    weights = model.init_weights(config, args.seed,
                                 adapter.weight_rows(config))
    engine = adapter.build_engine(config, weights)
    rng = np.random.default_rng(args.seed)
    driver.warm_up(engine, cell.traffic, config, rng)
    for i, rate in enumerate(args.rates):
        streams = traffic.generate(cell.traffic, args.seed + i, args.seconds,
                                   config["vocab_size"], rate=rate)
        for s in streams:       # request ids stay unique across windows
            s.sid += i * SID_STRIDE
        recs, steps, t_open, t_close = driver.drive(
            engine, streams, args.seconds, False, cli.no_span)
        t_last = driver.clock()
        # Requests the drain limit cut go, so the next window starts empty.
        for r in recs.values():
            if r.req is not None and not r.req.done:
                engine.cancel(r.req.id)
        while adapter.busy(engine):
            engine.step()
        counted = sorted((r for r in recs.values() if r.counted),
                         key=lambda r: r.due)
        ttft = [(r.first if r.first is not None else t_last) - r.due
                for r in counted]
        third = max(1, len(ttft) // 3)
        print(json.dumps({
            "rate": rate, "due": len(counted),
            "finished": sum(r.status == "finished" for r in counted),
            "due_per_s": len(counted) / args.seconds,
            "ttft_p50_s": results.percentile(ttft, 50),
            "ttft_p90_s": results.percentile(ttft, 90),
            "ttft_p50_first_third": results.percentile(ttft[:third], 50),
            "ttft_p50_last_third": results.percentile(ttft[-third:], 50),
            "tpot_p90_ms": (results.percentile(
                [(r.last - r.first) / (r.n - 1) for r in counted
                 if r.last and r.first and r.n > 1], 90) or 0) * 1000,
            "drain_s": t_last - t_close,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
