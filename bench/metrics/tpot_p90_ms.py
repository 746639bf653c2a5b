"""90th percentile over requests of (last token - first token) / (n - 1),
in milliseconds, every request due in the window with two tokens or more."""
from harness.results import percentile


def read(run):
    p = None if run.closed else percentile(run.tpots(), 90)
    return None if p is None else 1000.0 * p
