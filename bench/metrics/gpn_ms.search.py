"""Device time per traced window of the graph parsing network (edge scores,
dominant edges, the components loop, pooling): the leaf operations in the
program's ``repro.gpn`` scope, in the rollout and replay programs together
(union of intervals)."""
from bench import program_trace


def read(ctx):
    pt = program_trace.load(ctx)
    if pt is None or not ctx["windows"]:
        return None
    ns = pt.scope_ns("repro.gpn")
    return ns * 1e-6 / ctx["windows"] if ns else None
