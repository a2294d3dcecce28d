"""Mean host time of one ``Mixer.admit`` in the window (batch-1 prefill,
slot write and the first token's read-back), in ms."""


def read(ctx):
    admits = [b - a for a, b, _ in ctx["rec"].admits
              if ctx["rec"].t0 <= a < ctx["rec"].t_end]
    return 1e3 * sum(admits) / len(admits) if admits else None
