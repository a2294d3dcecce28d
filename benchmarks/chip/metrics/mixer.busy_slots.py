"""Mean number of occupied slots per decode step in the window."""


def read(ctx):
    steps = [n for a, _, n in ctx["rec"].steps
             if ctx["rec"].t0 <= a < ctx["rec"].t_end]
    return sum(steps) / len(steps) if steps else None
