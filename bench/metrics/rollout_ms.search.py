"""Device time per call of the sample-and-score window program (the
one-chip engine's ``rollout_window``, the mesh engine's ``_rollout``)."""


def read(ctx):
    seconds, calls = ctx["trace"].module_time(r"rollout_window|jit__rollout")
    return 1e3 * seconds / calls if calls else None
