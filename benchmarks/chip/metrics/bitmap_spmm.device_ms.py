"""Device time of the Mosaic kernels (``bitmap_spmm``) per decode step, in
ms: the kernel events inside the traced ``bench.step`` annotations.

Not a roofline share: XLA stages some payload operands into VMEM with copy
ops of its own before the kernel starts, so a kernel event does not hold
all of its HBM traffic (``decode_step_roofline`` bounds the whole step)."""


def read(ctx):
    steps = ctx["trace"].steps
    if not steps or not any(s.kernels for s in steps):
        return None
    return 1e-6 * sum(s.kernel_ns for s in steps) / len(steps)
