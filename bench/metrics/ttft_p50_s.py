"""Median time from a request being due to its first token reaching
the host, over every request due in the window."""
from harness.results import percentile


def read(run):
    return None if run.closed else percentile(run.ttfts(), 50)
