"""Useful FLOPs of the prompt tokens prefilled in the traced steps (from
shapes: suffix tokens only, causal attention behind the cached prefix, one
row of logits each) over the prefill program's device time at peak."""


def read(run):
    _, s = run.program("prefill")
    if not s:
        return None
    flops = run.prefill_flops()
    return 100.0 * flops / (s * run.peak_flops) if flops else None
