"""Share of what an unbounded read would take that the sliding-window
layers' decode reads: 100 x decode_window_positions_read /
decode_window_positions_dense (min(context, window) against the whole
context per decoded token), from the engine's counters at the window's
edges.  None where the engine does not count them or no window-layer
decode ran."""


def read(run):
    if "decode_window_positions_dense" not in run.counters_close:
        return None
    dense = run.counter("decode_window_positions_dense")
    if not dense:
        return None
    return 100.0 * run.counter("decode_window_positions_read") / dense
