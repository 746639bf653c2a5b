"""Share of the expert rows computed that a token picked: 100 x
moe_picks_held / moe_rows_computed (picks of valid tokens on the experts
held here, against dispatch rows x held experts, over every layer), from
the engine's counters at the window's edges.  None where the engine does
not count them or no MoE layer ran."""


def read(run):
    if "moe_rows_computed" not in run.counters_close:
        return None
    rows = run.counter("moe_rows_computed")
    return 100.0 * run.counter("moe_picks_held") / rows if rows else None
