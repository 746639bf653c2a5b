"""Share of prompt tokens served from cached prefix pages in the window:
prefix_tokens_shared / (prefix_tokens_shared + prefill_work_tokens), from
the engine's counters at the window's edges."""


def read(run):
    shared = run.counter("prefix_tokens_shared")
    total = shared + run.counter("prefill_work_tokens")
    return 100.0 * shared / total if total else None
