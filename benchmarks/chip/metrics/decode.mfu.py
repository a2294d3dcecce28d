"""Share of the chip's bf16 peak that the window's output tokens required,
%: the FLOPs the tokens decoded in the window needed, each at its live
context, as the configuration's architecture counts them
(``ctx["decode_flops"]``, from its module's ``decode_flops``), over the
window's host-clock length and the peak."""


def read(ctx):
    rec = ctx["rec"]
    if not rec.ctx:
        return None
    return 100.0 * ctx["decode_flops"] / (rec.t_end - rec.t0) \
        / ctx["peak"]["flops_bf16"]
