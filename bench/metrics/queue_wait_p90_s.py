"""90th percentile of the engine's own queue wait (submit to first
admission, Request.queue_wait_s) over the requests due in the window."""
from harness.results import percentile


def read(run):
    return percentile((r.req.queue_wait_s for r in run.records
                       if r.req is not None
                       and r.req.queue_wait_s is not None), 90)
