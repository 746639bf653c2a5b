"""Device time of the decode-chunk program per decode step."""
from harness.results import decode_step_ms as read  # noqa: F401
