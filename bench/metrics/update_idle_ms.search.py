"""Device idle time per traced window while the host was inside the
program's optimizer step (the ``repro.optim.update`` span of
``HSDAG.apply_grads``)."""
from bench import program_trace

SPAN = "repro.optim.update"


def read(ctx):
    pt = program_trace.load(ctx)
    if pt is None or not ctx["windows"] or SPAN not in pt.spans:
        return None
    return pt.idle_in_ns([SPAN]) * 1e-6 / ctx["windows"]
