"""Policy-engine unit + hypothesis property tests: the paper's invariants.

Requires the optional ``hypothesis`` dev dependency (requirements-dev.txt);
the module skips gracefully when it is absent.  The deterministic planner /
sweep invariants live in ``test_planner_sweep.py`` and always run.
"""
import pytest

pytest.importorskip("hypothesis")

import hypothesis.strategies as st
from hypothesis import given, settings

from repro import hw
from repro.core import Policy, StaticMode, WorkloadClass, make_engine
from repro.core.allocator import mxu_efficiency, plan_op
from repro.core.characterize import (
    attention_op,
    classify_workload,
    elementwise_op,
    matmul_op,
    rowwise_op,
    window_op,
)
from repro.core.cost_model import (
    adaptive_assignment,
    op_cost,
    workload_cost,
)
from repro.core.policy import static_assignment
from repro.core.predictor import PolicyPredictor, SiteKey


# ---------------------------------------------------------------------------
# Allocation-Bypass (allocator) properties
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(
    m=st.integers(1, 16384), k=st.integers(1, 16384), n=st.integers(1, 16384),
    mode=st.sampled_from([StaticMode.UNCACHED, StaticMode.CACHER,
                          StaticMode.CACHERW]),
    ab=st.booleans(),
)
def test_allocator_never_exceeds_budget(m, k, n, mode, ab):
    op = matmul_op(m, k, n)
    plan = plan_op(op, static_assignment(op, mode), chip=hw.V5E,
                   allocation_bypass=ab)
    assert plan.vmem_bytes <= hw.V5E.vmem_budget
    # MXU-aligned (or dim-limited) block shapes.
    for dim, b in plan.block.items():
        assert b >= 1


@settings(max_examples=30, deadline=None)
@given(m=st.integers(256, 8192), k=st.integers(256, 8192),
       n=st.integers(256, 8192))
def test_allocation_bypass_demotes_instead_of_shrinking(m, k, n):
    """With AB, residency pressure resolves by demotion (bypass), keeping
    MXU-efficient tiles; without, tiles shrink (stall events)."""
    op = matmul_op(m, k, n)
    a = static_assignment(op, StaticMode.CACHERW)
    with_ab = plan_op(op, a, allocation_bypass=True)
    without = plan_op(op, a, allocation_bypass=False)
    assert with_ab.shrink_events == 0 or not with_ab.demotions
    assert mxu_efficiency(with_ab) >= mxu_efficiency(without) - 1e-9


def test_blocking_baseline_records_stalls():
    # Force residency whose reuse band (bk x N) far exceeds VMEM.
    op = matmul_op(1024, 8192, 2_000_000)
    a = static_assignment(op, StaticMode.CACHERW)
    plan = plan_op(op, a, allocation_bypass=False)
    assert plan.shrink_events > 0
    plan_ab = plan_op(op, a, allocation_bypass=True)
    assert plan_ab.shrink_events == 0
    assert plan_ab.demotions


# ---------------------------------------------------------------------------
# Cost model properties
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(elems=st.integers(1 << 10, 1 << 28))
def test_elementwise_caching_never_helps(elems):
    """Zero-reuse ops: Uncached is always <= cached times (paper's
    throughput-sensitive finding)."""
    op = elementwise_op(elems, dtype="f32")
    unc = op_cost(op, mode=StaticMode.UNCACHED, chip=hw.PAPER_GPU,
                  allocation_bypass=False, rinse=False)
    crw = op_cost(op, mode=StaticMode.CACHERW, chip=hw.PAPER_GPU,
                  allocation_bypass=False, rinse=False)
    assert unc.t_total <= crw.t_total + 1e-12


@settings(max_examples=60, deadline=None)
@given(rows=st.integers(1, 4096), row_len=st.integers(2, 8192),
       passes=st.integers(2, 5))
def test_realizable_reuse_reduces_traffic(rows, row_len, passes):
    op = rowwise_op(rows, row_len, passes=passes, dtype="f32")
    unc = op_cost(op, mode=StaticMode.UNCACHED, chip=hw.PAPER_GPU)
    cr = op_cost(op, mode=StaticMode.CACHER, chip=hw.PAPER_GPU)
    assert cr.hbm_bytes <= unc.hbm_bytes + 1e-9


@settings(max_examples=40, deadline=None)
@given(
    elems=st.integers(1 << 21, 1 << 26),
    window=st.integers(2, 9),
)
def test_unrealizable_reuse_gains_nothing(elems, window):
    """Reuse whose window exceeds capacity is captured at most in
    proportion to budget/window (FwLRN: window >> L2 -> ~no gain)."""
    op = window_op(elems, window, 1, reuse_distance_elems=elems // 2,
                   dtype="f32")
    unc = op_cost(op, mode=StaticMode.UNCACHED, chip=hw.PAPER_GPU)
    cr = op_cost(op, mode=StaticMode.CACHER, chip=hw.PAPER_GPU)
    x = op.operand("x")
    frac_max = min(1.0, hw.PAPER_GPU.vmem_budget / x.window_bytes)
    min_traffic = unc.read_bytes - (
        (x.touched_bytes_stream - x.unique_bytes) * frac_max
    )
    assert cr.read_bytes >= min_traffic * 0.99


@settings(max_examples=40, deadline=None)
@given(
    m=st.integers(64, 4096), k=st.integers(64, 4096), n=st.integers(64, 4096),
)
def test_adaptive_never_worse_than_best_static(m, k, n):
    """The paper's headline: AB+CR+PCby matches the best static policy."""
    ops = [matmul_op(m, k, n, dtype="f32", bm=64, bn=64, bk=64)]
    times = {
        mode: workload_cost(ops, mode=mode, chip=hw.PAPER_GPU,
                            launches_per_op=0).t_total
        for mode in StaticMode
    }
    best_static = min(
        times[m_] for m_ in (StaticMode.UNCACHED, StaticMode.CACHER,
                             StaticMode.CACHERW)
    )
    assert times[StaticMode.ADAPTIVE] <= best_static * 1.05


def test_rinse_improves_write_contiguity():
    op = matmul_op(4096, 4096, 4096, split_k=4)
    a = static_assignment(op, StaticMode.CACHERW)
    no_rinse = op_cost(op, assignment=a, rinse=False, allocation_bypass=True)
    rinse = op_cost(op, assignment=a, rinse=True, allocation_bypass=True)
    assert rinse.write_contiguity >= no_rinse.write_contiguity
    assert rinse.t_total <= no_rinse.t_total + 1e-12


# ---------------------------------------------------------------------------
# Classification
# ---------------------------------------------------------------------------

def test_paper_workload_classification():
    from repro.workloads.suite import SUITE

    mismatches = {
        name: (w.expected, classify_workload(w.ops, chip=hw.PAPER_GPU))
        for name, w in SUITE.items()
        if classify_workload(w.ops, chip=hw.PAPER_GPU) != w.expected
    }
    assert not mismatches, mismatches


def test_adaptive_matches_best_static_on_suite():
    from repro.workloads.suite import SUITE

    for name, w in SUITE.items():
        times = {
            mode: workload_cost(w.ops, mode=mode, chip=hw.PAPER_GPU,
                                launches_per_op=0).t_total
            for mode in StaticMode
        }
        best = min(times[m] for m in (StaticMode.UNCACHED, StaticMode.CACHER,
                                      StaticMode.CACHERW))
        assert times[StaticMode.ADAPTIVE] <= best * 1.05, (name, times)


def test_classification_matches_on_tpu_chip_for_elementwise():
    op = elementwise_op(1 << 28, dtype="bf16")
    assert classify_workload([op], chip=hw.V5E) in (
        WorkloadClass.THROUGHPUT_SENSITIVE, WorkloadClass.MEMORY_INSENSITIVE
    )


# ---------------------------------------------------------------------------
# Predictor (PCby analogue)
# ---------------------------------------------------------------------------

def test_predictor_seeded_from_cost_model():
    from repro.core.sweep import optimal_assignment

    p = PolicyPredictor(chip=hw.V5E)
    op = matmul_op(2048, 2048, 2048)
    a = p.predict(op)
    # Seeded from the exact lattice optimum, which keeps the greedy choice
    # on ties — and for this op the greedy walk is already optimal.
    assert a == optimal_assignment(op, hw.V5E)
    assert a == adaptive_assignment(op, hw.V5E)
    t_seed = op_cost(op, assignment=a, chip=hw.V5E, launches=0).t_total
    t_greedy = op_cost(op, assignment=adaptive_assignment(op, hw.V5E),
                       chip=hw.V5E, launches=0).t_total
    assert t_seed <= t_greedy


def test_predictor_flips_on_negative_feedback():
    p = PolicyPredictor(chip=hw.V5E)
    op = rowwise_op(512, 1024, passes=3)
    a = p.predict(op)
    assert a["x"] is Policy.RESIDENT
    for _ in range(4):
        p.update(op, a, benefit=-0.5)
    assert p.predict(op)["x"] is Policy.STREAM


def test_predictor_persistence_roundtrip(tmp_path):
    p = PolicyPredictor()
    op = matmul_op(512, 512, 512)
    p.predict(op)
    path = str(tmp_path / "policies.json")
    p.save(path)
    q = PolicyPredictor().load(path)
    assert len(q) == len(p)
    assert q.predict(op) == p.predict(op)


def test_engine_feedback_converges_to_best_static():
    """Simulated closed loop: feed modeled times back; adaptive ends at or
    below the best static cost for a mixed workload (paper Fig 10)."""
    eng = make_engine(chip="gem5-apu")
    ops = [
        elementwise_op(1 << 26, dtype="f32", name="act"),
        matmul_op(512, 4096, 4096, dtype="f32", bm=64, bn=64, bk=64),
        rowwise_op(4096, 4096, passes=3, dtype="f32"),
    ]
    for _ in range(6):
        for op in ops:
            plan = eng.plan_op(op)
            eng.feedback(op, plan, eng.cost(op, plan).t_total)
    for op in ops:
        best = min(
            workload_cost([op], mode=m, chip=hw.PAPER_GPU,
                          launches_per_op=1).t_total
            for m in (StaticMode.UNCACHED, StaticMode.CACHER,
                      StaticMode.CACHERW)
        )
        assert eng.cost(op).t_total <= best * 1.1


# ---------------------------------------------------------------------------
# SiteKey hygiene
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(m=st.integers(1, 8192), k=st.integers(1, 8192), n=st.integers(1, 8192))
def test_sitekey_encode_roundtrip(m, k, n):
    op = matmul_op(m, k, n)
    for o in op.operands:
        key = SiteKey.from_profile(op, o)
        assert SiteKey.decode(key.encode()) == key


def test_kv_policy_decision():
    eng = make_engine()
    # Tiny per-layer KV (whisper cross K/V scale): resident.
    assert eng.kv_policy(2 * 1024 * 1024) is Policy.RESIDENT
    # Multi-GB decode cache: stream.
    assert eng.kv_policy(4 * 1024**3) is Policy.STREAM
