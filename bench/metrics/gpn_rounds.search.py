"""Propagation passes of the GPN's connected-components loop per rollout
step: executions of the loop body in the ``repro.gpn_components`` scope of
the rollout program, per execution of the loop, plus the one pass before it
(the loop's own ``it`` + 1; under ``vmap`` the slowest chain's)."""
from bench import program_trace


def read(ctx):
    pt = program_trace.load(ctx)
    if pt is None:
        return None
    loops, body = pt.loop_passes("repro.gpn_components")
    return body / loops + 1.0 if loops else None
