"""Device time of the admission-prefill program per execution (one per
admission wave), from the device trace."""


def read(run):
    n, s = run.program("prefill")
    return 1000.0 * s / n if n else None
