"""Walks of the in-jit simulator per traced window: executions of the
``while`` loops in the ``repro.sim`` scope of the rollout program (the
``scan`` backend's node scan, one execution per walk over the graph's
nodes), per window."""
from bench import program_trace


def read(ctx):
    pt = program_trace.load(ctx)
    if pt is None or not ctx["windows"]:
        return None
    loops, _ = pt.loop_passes("repro.sim")
    return loops / ctx["windows"] if loops else None
