"""CachePolicyEngine — the paper's adaptive mechanism as one composable object.

Pipeline per op: characterize (OpSpec) -> predict (PCby site table) ->
allocate (AB non-blocking VMEM planner) -> rinse (grid/flush order).
Output: a :class:`KernelPlan` consumed by the Pallas kernels, plus modeled
cost for reporting/feedback.

Planning is memoized (DESIGN.md §3): every plan/cost query routes through a
:class:`~repro.core.planner.Planner`, so an op that launches repeatedly
(RNN cells, each transformer layer) plans once and hits the
:class:`~repro.core.planner.PlanCache` thereafter.  ``plan_stats()``
exposes the hit/miss counters.

The engine also owns the trainer-level activation policy (remat) and is the
single switch between the paper's static baselines and the adaptive mode.
"""
from __future__ import annotations

import dataclasses

from repro import hw
from repro.core import allocator
from repro.core.planner import PlanCache, Planner
from repro.core.policy import (
    Assignment,
    KernelPlan,
    OpSpec,
    Policy,
    StaticMode,
    static_assignment,
)
from repro.core.predictor import PolicyPredictor
from repro.models import remat


@dataclasses.dataclass
class EngineConfig:
    mode: StaticMode = StaticMode.ADAPTIVE
    allocation_bypass: bool = True
    rinse: bool = True
    chip_name: str = "tpu-v5e"

    @property
    def chip(self) -> hw.Chip:
        return hw.PAPER_GPU if self.chip_name == "gem5-apu" else hw.V5E


class CachePolicyEngine:
    def __init__(
        self,
        config: EngineConfig | None = None,
        predictor: PolicyPredictor | None = None,
        plan_cache: PlanCache | None = None,
    ):
        self.config = config or EngineConfig()
        self.chip = self.config.chip
        self.planner = Planner(chip=self.chip, cache=plan_cache)
        self.predictor = predictor or PolicyPredictor(
            chip=self.chip, planner=self.planner
        )

    # -- per-op planning ----------------------------------------------------

    def assign(self, op: OpSpec) -> Assignment:
        if self.config.mode is StaticMode.ADAPTIVE:
            return self.predictor.predict(
                op,
                allocation_bypass=self.config.allocation_bypass,
                rinse=self.config.rinse,
            )
        return static_assignment(op, self.config.mode)

    def plan_op(self, op: OpSpec) -> KernelPlan:
        return self.planner.plan(
            op,
            self.assign(op),
            allocation_bypass=self.config.allocation_bypass,
            rinse=self.config.rinse,
        )

    def cost(self, op: OpSpec, plan: KernelPlan | None = None):
        plan = plan or self.plan_op(op)
        breakdown = self.planner.cost(
            op,
            assignment=plan.assignment,
            allocation_bypass=self.config.allocation_bypass,
            rinse=self.config.rinse,
        )
        # Fold MXU starvation from shrunken tiles into compute time.
        eff = allocator.mxu_efficiency(plan, self.chip)
        breakdown.t_compute /= eff
        breakdown.t_total = (
            max(breakdown.t_compute, breakdown.t_hbm) + breakdown.t_overhead
        )
        return breakdown

    def feedback(self, op: OpSpec, plan: KernelPlan, measured_time: float) -> None:
        """Close the loop: compare against the bypass baseline and update
        the predictor's confidence counters."""
        baseline = self.planner.cost(
            op, mode=StaticMode.UNCACHED
        ).t_total
        benefit = (baseline - measured_time) / max(baseline, 1e-30)
        self.predictor.update(op, plan.assignment, benefit)

    # -- cache visibility ----------------------------------------------------

    @property
    def plan_cache(self) -> PlanCache:
        return self.planner.cache

    def plan_stats(self) -> dict:
        return self.planner.stats()

    # -- trainer-level activation policy ------------------------------------

    def remat_policy(
        self,
        activation_bytes_per_layer: float,
        n_layers: int,
        hbm_free_bytes: float | None = None,
    ) -> remat.RematPolicy:
        free = self.chip.hbm_bytes * 0.6 if hbm_free_bytes is None else hbm_free_bytes
        return remat.choose_policy(activation_bytes_per_layer, n_layers, free)

    # -- reporting -----------------------------------------------------------

    def kv_policy(self, kv_bytes_per_layer: int) -> Policy:
        """Serving-side: keep a layer's KV block resident in VMEM during the
        decode kernel only if it fits the budget share; else stream it."""
        if kv_bytes_per_layer <= self.chip.vmem_budget // 4:
            return Policy.RESIDENT
        return Policy.STREAM


def make_engine(
    mode: str = "adaptive",
    allocation_bypass: bool = True,
    rinse: bool = True,
    chip: str = "tpu-v5e",
    plan_cache: PlanCache | None = None,
) -> CachePolicyEngine:
    return CachePolicyEngine(
        EngineConfig(
            mode=StaticMode(mode),
            allocation_bypass=allocation_bypass,
            rinse=rinse,
            chip_name=chip,
        ),
        plan_cache=plan_cache,
    )
