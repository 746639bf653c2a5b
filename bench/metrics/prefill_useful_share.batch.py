"""Share of the prefill rows computed in the window that held a prompt
token: prefill_work_tokens / prefill_rows_computed (slots x pad bucket per
admission wave), from the engine's counters at the window's edges.  None
where the engine does not count the rows."""


def read(run):
    if "prefill_rows_computed" not in run.counters_close:
        return None
    rows = run.counter("prefill_rows_computed")
    return 100.0 * run.counter("prefill_work_tokens") / rows if rows else None
