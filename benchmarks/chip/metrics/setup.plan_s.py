"""Host seconds of ``repro.exec.build_exec_plan``: the co-search of format
and dataflow, paid at every start."""


def read(ctx):
    return ctx["plan_s"]
