"""Find a cell's pieces by name: BENCHMARK.json at the checkout's root, the
configuration file it names, ``traffic/<traffic>.json`` and
``metrics/<metric>.py`` beside this package.  A new configuration, traffic
mix or per-layer metric is new files plus entries in BENCHMARK.json; nothing
here changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    config_name: str
    traffic: dict         # the traffic file's contents
    traffic_name: str
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list
    run_seconds: int


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str | None = None,
              bench: str | None = None) -> Cell:
    root, bench = root or ROOT, bench or BENCH
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(
        name=name, chips=w["chips"], config=config, config_name=cfg["name"],
        traffic=traffic, traffic_name=w["traffic"],
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
        run_seconds=spec["run_seconds"],
    )


def load_module(path: str, name: str):
    """Import a file by path (metric readers and model files are found by
    the name BENCHMARK.json or a configuration gives them)."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(metric: str, bench: str | None = None):
    """``metrics/<metric>.py``'s ``read(run) -> float | None``."""
    bench = bench or BENCH
    path = os.path.join(bench, "metrics", metric + ".py")
    return load_module(path, "bench_metric_" + metric.replace(".", "_")).read


def model_module(config: dict, bench: str | None = None):
    """``models/<config["model"]>.py``: weights, reference, FLOP counts."""
    bench = bench or BENCH
    path = os.path.join(bench, "models", config["model"] + ".py")
    return load_module(path, "bench_model_" + config["model"])
