"""Tokens delivered to the host by the steps that returned inside the
window, over the window's length."""


def read(run):
    steps = run.window_steps()
    return sum(run.steps.tokens[i] for i in steps) / run.window_s
