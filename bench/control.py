#!/usr/bin/env python3
"""Readings the correctness limit is set from, for one cell on the chip:

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed, in one process: weights from the seed, the cell's traffic
served for ``--seconds`` through the engine, and the same sample of finished
streams the benchmark checks.  Over that sample it prints the program's
widest logit gap (served token against the float32 reference's best) and
the control's: the reference computed with every matmul operand rounded
to float8 (the precision below the configuration's bfloat16), reading at
each position the gap of the token the float8 forward puts first.  The
limit in the configuration file lies between the program's largest reading
and the control's smallest.  The benchmark's own runs do not run this.
"""
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seed: int, seconds: float):
    import numpy as np

    from harness import adapter, check, cli, driver, spec, traffic

    config = cell.config
    model = spec.model_module(config)
    weights = model.init_weights(config, seed, adapter.weight_rows(config))
    engine = adapter.build_engine(config, weights)
    driver.warm_up(engine, cell.traffic, config,
                   np.random.default_rng([seed % (2 ** 64), 0x3A53]))
    streams = traffic.generate(cell.traffic, seed, seconds,
                               config["vocab_size"])
    closed = cell.traffic["arrivals"]["process"] == "closed"
    recs = {}
    if closed:
        driver.ramp(engine, streams, recs, driver.clock)
    recs, *_ = driver.drive(engine, streams, seconds, closed,
                            cli.no_span, None, recs)
    finished = [r for r in recs.values() if r.status == "finished"]
    adapter.release(engine)
    del engine
    picked = check.sample(finished, seed)
    gap_fn = model.make_gap_fn(config, control=True)
    cap = config["deployment"]["max_len"]
    prog = check.run_check(gap_fn, weights, picked, config["vocab_size"],
                           cap, gaps_of=lambda out: out[0])
    ctl = check.run_check(gap_fn, weights, picked, config["vocab_size"],
                          cap, gaps_of=lambda out: out[1])
    limit = config["check"]["max_logit_gap"]
    del weights, gap_fn
    return {"seed": seed, "requests": prog.requests, "tokens": prog.tokens,
            "program_worst_gap": prog.worst_gap,
            "control_worst_gap": ctl.worst_gap,
            "bad_streams": prog.bad_streams, "limit": limit,
            "program_correct": prog.ok(limit),
            "control_correct": ctl.ok(limit)}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax

    from harness import cli, compiles, spec

    cell = spec.load_cell(args.workload)
    try:
        cli.require_chips(jax, cell.chips)
    except RuntimeError as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    compiles.use_cache(jax, spec.ROOT)
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        row = readings(cell, seed, args.seconds)
        gc.collect()        # the seed's weights and engine go first
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {
        "workload": args.workload,
        "program_max": max(r["program_worst_gap"] for r in rows),
        "control_min": min(r["control_worst_gap"] for r in rows),
        "rows": rows,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
