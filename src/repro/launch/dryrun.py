import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST come before jax is imported: jax locks the platform and device
# count on first init.  This is a compile-only tool: 512 placeholder host
# devices stand in for 2 pods x 256 chips, and pinning the CPU keeps it and
# its --sweep children (which inherit this environment) off any attached
# accelerator.  Set ONLY here — tests and benches see their own devices.

"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh) cell.

For each cell we jit the real step function (train_step / prefill /
decode_step) with production shardings over the 16x16 single-pod or 2x16x16
multi-pod mesh, ``.lower().compile()`` it against ShapeDtypeStruct inputs
(no allocation), and record:

* ``compiled.memory_analysis()``  — proves the cell fits per-device HBM,
* ``compiled.cost_analysis()``    — HLO FLOPs / bytes for §Roofline,
* collective bytes parsed from the optimized HLO (all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute).

Usage:
    python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
    python -m repro.launch.dryrun --sweep            # all cells, subprocesses
"""
import argparse
import json
import subprocess
import sys
import time
import traceback

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import hw
from repro.configs.base import SHAPES
from repro.core.remat import RematPolicy
from repro.distributed import sharding as sh
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.models import build_model, get_config, runnable_cells
from repro.train import optimizer as opt
from repro.train.step import TrainConfig, make_train_step

ARTIFACT_DIR = "artifacts/dryrun"


def _cost_dict(compiled) -> dict:
    return dict(compiled.cost_analysis() or {})


def plan_model_policies(cfg, shape, plan_cache=None) -> dict:
    """Plan VMEM policies for the model's per-layer op graph through the
    memoized CachePolicyEngine (DESIGN.md §3).

    Characterizes each transformer layer's ops (norms, QKV/O projections,
    attention, MLP matmuls) as OpSpecs and plans all ``n_layers`` of them:
    every layer after the first hits the PlanCache, so the reported
    ``hit_rate`` is ~(L-1)/L per distinct op — the artifact's proof that
    repeated layers plan once.
    """
    from repro.core import make_engine
    from repro.core.characterize import attention_op, matmul_op, rowwise_op
    from repro.core.planner import PlanCache

    eng = make_engine(plan_cache=plan_cache or PlanCache())
    b = max(1, shape.global_batch // hw.CHIPS_PER_POD)   # per-chip slice
    s = 1 if shape.kind == "decode" else shape.seq_len
    d, f = cfg.d_model, cfg.d_ff
    hq, hkv, dh = cfg.n_heads, max(1, cfg.n_kv_heads), cfg.head_dim_
    tokens = b * s
    layer_ops = [rowwise_op(tokens, d, passes=2, name="ln_in")]
    if hq and dh:
        layer_ops += [
            matmul_op(tokens, d, (hq + 2 * hkv) * dh, name="qkv_proj"),
            attention_op(b, hq, hkv, s, shape.seq_len, dh, name="attn"),
            matmul_op(tokens, hq * dh, d, name="o_proj"),
        ]
    if f:
        layer_ops += [
            rowwise_op(tokens, d, passes=2, name="ln_mlp"),
            matmul_op(tokens, d, f, name="mlp_up"),
            matmul_op(tokens, f, d, name="mlp_down"),
        ]
    policies = {}
    vmem_peak = 0
    for _ in range(max(1, cfg.n_layers)):
        for op in layer_ops:
            plan = eng.plan_op(op)
            eng.cost(op, plan)
            vmem_peak = max(vmem_peak, plan.vmem_bytes)
            policies[op.name] = {
                o.name: plan.assignment[o.name].value for o in op.operands
            }
    stats = eng.plan_stats()
    return {
        "layers": cfg.n_layers,
        "ops_per_layer": len(layer_ops),
        "ops_planned": max(1, cfg.n_layers) * len(layer_ops),
        "plan_cache_hit_rate": stats["hit_rate"],
        "plan_cache": stats,
        "vmem_peak_bytes": vmem_peak,
        "policies": policies,
    }


def _tree_shardings(tree, mesh, spec_fn):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: NamedSharding(mesh, spec_fn(path, x)), tree
    )


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    remat: str = "save_dots",
    grad_reduce_dtype: str = "float32",
    microbatch: int = 1,
    zero1: bool = False,
    fsdp: str = "auto",
    moe_dispatch: str = "dense",
    cfg=None,
):
    cfg = cfg or get_config(arch)
    if moe_dispatch != "dense":
        import dataclasses as _dc

        cfg = _dc.replace(cfg, moe_dispatch=moe_dispatch)
    model = build_model(cfg)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    specs = input_specs(arch, shape_name, model=model, cfg=cfg)
    use_fsdp = (
        sh.fsdp_needed(cfg, mesh, train=shape.kind == "train")
        if fsdp == "auto" else fsdp in (True, "on", "true")
    )

    if specs["kind"] == "train":
        tcfg = TrainConfig(
            remat=RematPolicy(remat),
            grad_reduce_dtype=grad_reduce_dtype,
            microbatch=microbatch,
            zero1=zero1,
            batch_axes=tuple(sh.batch_axes(mesh)),
        )
        train_step, _ = make_train_step(cfg, tcfg)
        pshard = sh.params_shardings(specs["state"]["params"], cfg, mesh, fsdp=use_fsdp)
        oshard = opt.opt_shardings(
            pshard, specs["state"]["params"], mesh, zero1=zero1
        )
        state_shardings = {"params": pshard, "opt": oshard}
        bspec = sh.batch_spec(cfg, mesh, shape.global_batch)
        batch_shardings = {
            k: NamedSharding(mesh, bspec[k]) for k in specs["batch"]
        }
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                train_step,
                in_shardings=(state_shardings, batch_shardings),
                out_shardings=(state_shardings, None),
                donate_argnums=(0,),
            ).lower(specs["state"], specs["batch"])
    else:
        long_ctx = shape_name == "long_500k"
        pshard = sh.params_shardings(specs["params"], cfg, mesh, fsdp=use_fsdp)
        cspec_fn = sh.cache_spec(
            cfg, mesh, shape.global_batch, long_context=long_ctx
        )
        cshard = _tree_shardings(specs["cache"], mesh, cspec_fn)
        b = sh._batch_rule(mesh, shape.global_batch)
        tok_shard = NamedSharding(mesh, P(b, None))

        step = model.prefill if specs["kind"] == "prefill" else model.decode_step
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(pshard, cshard, tok_shard),
                out_shardings=(None, cshard),
                donate_argnums=(1,),
            ).lower(specs["params"], specs["cache"], specs["tokens"])
    return cfg, shape, mesh, lowered


def _layer_unit(cfg) -> int:
    return cfg.cross_attn_every or cfg.shared_attn_every or 1


def counted_metrics(arch: str, shape_name: str, multi_pod: bool, **knobs):
    """Trip-count-correct HLO FLOPs/bytes/collectives.

    XLA's cost_analysis counts a while (scan) body ONCE regardless of trip
    count, so the scanned full model under-reports.  We lower the SAME cell
    at 1 and 2 layer-units with every scan fully unrolled, then linearly
    extrapolate: metric(L) = base + L * per_unit.  Exact for costs linear in
    depth (all of ours are: per-layer compute/traffic/collectives + a
    depth-independent embed/unembed/optimizer base).
    """
    import dataclasses as dc

    from repro.models import common as model_common

    # Counting runs at microbatch=1: unrolling the grad-accumulation scan
    # multiplies HLO size by mb for ~0.1% traffic difference (params are
    # re-read per microbatch but are ~1e-3 of activation traffic here).
    knobs = dict(knobs, microbatch=1)
    cfg = get_config(arch)
    if knobs.get("moe_dispatch", "dense") != "dense":
        cfg = dc.replace(cfg, moe_dispatch=knobs["moe_dispatch"])
    unit = _layer_unit(cfg)
    units_real = cfg.n_layers // unit
    cfgs = []
    for k in (1, 2):
        c = dc.replace(cfg, n_layers=unit * k)
        if cfg.family == "encdec":
            c = dc.replace(c, enc_layers=k)
        cfgs.append(c)

    model_common.set_scan_unroll(True)
    try:
        measured = []
        for c in cfgs:
            _, shape, mesh, lowered = lower_cell(
                arch, shape_name, multi_pod, cfg=c, **knobs
            )
            compiled = lowered.compile()
            cost = _cost_dict(compiled)
            colls = roofline.parse_collectives(compiled.as_text())
            measured.append({
                "flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0)),
                "coll_moved": colls["total_moved_bytes"],
                "coll_count": colls["total_count"],
                "coll_per_kind": {
                    k: v["moved_bytes"] for k, v in colls["per_kind"].items()
                },
            })
    finally:
        model_common.set_scan_unroll(False)

    m1, m2 = measured

    def extrap(a, b):
        per = b - a
        return (a - per) + units_real * per

    out = {k: extrap(m1[k], m2[k]) for k in ("flops", "bytes", "coll_moved",
                                             "coll_count")}
    out["coll_per_kind"] = {
        k: extrap(m1["coll_per_kind"][k], m2["coll_per_kind"][k])
        for k in m1["coll_per_kind"]
    }
    out["units"] = units_real
    out["measured_1unit"] = m1
    out["measured_2unit"] = m2
    return out


def analyze(cfg, shape, mesh, lowered, compile_s, compiled):
    n_chips = int(np.prod(list(mesh.shape.values())))
    cost = {}
    try:
        cost = _cost_dict(compiled)
    except Exception as e:  # pragma: no cover
        cost = {"error": str(e)}
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            if hasattr(ma, k):
                mem[k] = int(getattr(ma, k))
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}

    hlo = compiled.as_text()
    colls = roofline.parse_collectives(hlo)
    return {
        "arch": cfg.arch,
        "shape": shape.name,
        "mesh": dict(mesh.shape),
        "n_chips": n_chips,
        "compile_seconds": round(compile_s, 2),
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float)) and "{" not in k},
        "memory_analysis": mem,
        "collectives_scanned_module_raw": colls,
    }, cost, colls, mem


HBM_BYTES = 16 * 1024**3


def _fits(mem: dict) -> bool:
    need = mem.get("argument_size_in_bytes", 0) + mem.get("temp_size_in_bytes", 0)
    return bool(need and need <= HBM_BYTES)


def _prior_knobs(arch: str, shape_name: str, out_dir: str) -> dict | None:
    """Fitted knobs from the single-pod artifact (reused by multi-pod)."""
    path = os.path.join(out_dir, f"{arch}__{shape_name}__single.json")
    if os.path.exists(path):
        try:
            with open(path) as f:
                return json.load(f).get("knobs")
        except Exception:
            return None
    return None


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             tag: str = "", auto_fit: bool = True, counting: bool = True,
             **knobs) -> dict:
    """Compile one cell.  ``auto_fit`` escalates (microbatch, remat) like the
    allocation-bypass planner does for VMEM: never 'OOM-stall', demote the
    activation-residency policy / split the batch until the cell fits HBM.
    Multi-pod cells reuse the single-pod run's fitted knobs and skip the
    counting lowers (the roofline table is single-pod only)."""
    shape_kind = SHAPES[shape_name].kind
    if multi_pod and not tag:
        prior = _prior_knobs(arch, shape_name, out_dir)
        if prior:
            knobs = dict(knobs, **prior)
    if "fsdp" not in knobs or knobs["fsdp"] == "auto":
        # Resolve FSDP once per cell so the counting lowers (reduced-depth
        # configs) use the SAME sharding strategy as the artifact.
        class _M:
            shape = {"data": 16, "model": 16}

        knobs = dict(knobs, fsdp=sh.fsdp_needed(
            get_config(arch), _M, train=shape_kind == "train"
        ))
    if shape_kind == "train" and knobs.get("remat") == "save_dots" and (
        knobs.get("microbatch", 1) == 1
    ):
        # Baseline train config: recompute/mb4 (the save_dots/mb1 rung never
        # fits the 4k-seq 16GB-HBM cells; skipping it saves a compile).
        knobs = dict(knobs, remat="recompute", microbatch=4)
    ladder = [dict(knobs)]
    if auto_fit and shape_kind == "train":
        step_knobs = dict(knobs, remat="recompute",
                          microbatch=max(16, knobs.get("microbatch", 1)))
        if step_knobs not in ladder:
            ladder.append(step_knobs)

    result = cost = colls = mem = None
    # perf_counter, not time.time (R004): these are interval timings and
    # the wall clock is not monotonic under NTP steps.
    t0 = t1 = t2 = time.perf_counter()
    for i, kn in enumerate(ladder):
        t0 = time.perf_counter()
        cfg, shape, mesh, lowered = lower_cell(arch, shape_name, multi_pod, **kn)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        result, cost, colls, mem = analyze(cfg, shape, mesh, lowered, t2 - t1,
                                           compiled)
        del lowered, compiled
        knobs = kn
        if not auto_fit or shape_kind != "train" or _fits(mem):
            break
        if i < len(ladder) - 1:
            print(f"[dryrun] {arch} x {shape_name}: "
                  f"temp={mem.get('temp_size_in_bytes', 0)/2**30:.1f}GiB "
                  f"does not fit; escalating to {ladder[i+1]}", flush=True)
    # free before the counting lowers
    if counting:
        # Trip-count-correct costs from the reduced-depth unrolled lowers.
        counted = counted_metrics(arch, shape_name, multi_pod, **knobs)
        result["counted"] = {k: counted[k] for k in
                             ("flops", "bytes", "coll_moved", "coll_count",
                              "coll_per_kind", "units")}
        corrected_cost = {"flops": counted["flops"],
                          "bytes accessed": counted["bytes"]}
        corrected_colls = {"total_moved_bytes": counted["coll_moved"]}
        result["roofline"] = roofline.roofline_terms(
            cfg, shape, mesh, corrected_cost, corrected_colls, mem
        )
    else:
        # Multi-pod: compile-proof + memory only (roofline is single-pod).
        result["counted"] = {"coll_count": colls["total_count"]}
        result["roofline"] = {
            "fits_hbm": _fits(mem) if mem else None,
            "hbm_need_bytes": mem.get("argument_size_in_bytes", 0)
            + mem.get("temp_size_in_bytes", 0),
            "note": "multi-pod compile proof; roofline from single-pod",
        }
    try:
        result["policy_plan"] = plan_model_policies(cfg, shape)
    except Exception as e:  # report must never sink the compile proof
        result["policy_plan"] = {"error": str(e)}
    result["lower_seconds"] = round(t1 - t0, 2)
    result["knobs"] = knobs
    mesh_tag = "multi" if multi_pod else "single"
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_tag}{('__' + tag) if tag else ''}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(result, f, indent=1)
    print(f"[dryrun] {arch} x {shape_name} x {mesh_tag}: "
          f"compile={t2 - t1:.1f}s "
          f"dominant={result['roofline'].get('dominant')} "
          f"plan_hit_rate={result['policy_plan'].get('plan_cache_hit_rate', 'n/a')} "
          f"-> {fname}")
    # Required prints per the brief:
    print(json.dumps(result["memory_analysis"]))
    print(json.dumps(result["cost_analysis"]))
    return result


def sweep(out_dir: str, meshes=("single", "multi"), cells=None,
          timeout_s: int = 5400, jobs: int = 1):
    """Run every runnable cell in an isolated subprocess; JSON per cell."""
    from concurrent.futures import ThreadPoolExecutor

    cells = cells or runnable_cells()
    # Riskiest/heaviest archs first so failures surface early.
    risk = ["llama-3.2-vision-90b", "zamba2-2.7b", "mamba2-1.3b",
            "phi3.5-moe-42b-a6.6b", "whisper-small", "qwen2.5-32b"]
    cells = sorted(
        cells, key=lambda c: (risk.index(c[0]) if c[0] in risk else 99)
    )
    work = []
    for mesh_tag in meshes:
        for arch, shape_name in cells:
            fname = os.path.join(
                out_dir, f"{arch}__{shape_name}__{mesh_tag}.json"
            )
            if os.path.exists(fname):
                print(f"[sweep] skip existing {fname}")
                continue
            work.append((arch, shape_name, mesh_tag))

    failures = []

    def run_one(item):
        arch, shape_name, mesh_tag = item
        cmd = [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", arch, "--shape", shape_name, "--mesh", mesh_tag,
            "--out", out_dir,
        ] + (["--no-counting"] if mesh_tag == "multi" else [])
        print("[sweep]", " ".join(cmd), flush=True)
        try:
            r = subprocess.run(
                cmd, capture_output=True, text=True, timeout=timeout_s
            )
            if r.returncode != 0:
                failures.append((arch, shape_name, mesh_tag, r.stderr[-2500:]))
                print(f"[sweep] FAIL {arch} {shape_name} {mesh_tag}:\n"
                      f"{r.stderr[-2500:]}", flush=True)
            else:
                print(f"[sweep] OK {arch} {shape_name} {mesh_tag}", flush=True)
        except subprocess.TimeoutExpired:
            failures.append((arch, shape_name, mesh_tag, "timeout"))
            print(f"[sweep] TIMEOUT {arch} {shape_name} {mesh_tag}", flush=True)

    with ThreadPoolExecutor(max_workers=jobs) as pool:
        list(pool.map(run_one, work))
    print(f"[sweep] done, {len(failures)} failures")
    for f in failures:
        print("  FAIL:", f[0], f[1], f[2])
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--remat", default="save_dots",
                    choices=[p.value for p in RematPolicy])
    ap.add_argument("--grad-reduce-dtype", default="float32")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--zero1", action="store_true", default=True)
    ap.add_argument("--tag", default="")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--no-counting", action="store_true")
    ap.add_argument("--fsdp", default="auto", choices=["auto", "on", "off"])
    ap.add_argument("--moe-dispatch", default="dense", choices=["dense", "sorted"])
    args = ap.parse_args()

    if args.sweep:
        failures = sweep(args.out, jobs=args.jobs)
        sys.exit(1 if failures else 0)

    knobs = dict(
        remat=args.remat,
        grad_reduce_dtype=args.grad_reduce_dtype,
        microbatch=args.microbatch,
        zero1=args.zero1,
    )
    if args.fsdp != "auto":
        knobs["fsdp"] = args.fsdp == "on"
    if args.moe_dispatch != "dense":
        knobs["moe_dispatch"] = args.moe_dispatch
    try:
        run_cell(
            args.arch, args.shape, args.mesh == "multi", args.out,
            tag=args.tag, counting=not args.no_counting, **knobs,
        )
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
