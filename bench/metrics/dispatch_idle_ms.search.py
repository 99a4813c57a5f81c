"""Device idle time per traced window while the host was launching a window
program (the ``repro.rollout`` and ``repro.replay`` spans of
``RolloutEngine.rollout_window`` and ``window_grads``)."""
from bench import program_trace


def read(ctx):
    pt = program_trace.load(ctx)
    if pt is None or not ctx["windows"] or not any(
            s in pt.spans for s in program_trace.LAUNCH_SPANS):
        return None
    return pt.idle_in_ns(program_trace.LAUNCH_SPANS) * 1e-6 / ctx["windows"]
