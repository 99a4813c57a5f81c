"""Device time per call of the Eq.-14 replay and gradient program (the
one-chip engine's ``window_loss`` gradient, the mesh engine's ``_grads``)."""


def read(ctx):
    seconds, calls = ctx["trace"].module_time(r"window_loss|jit__grads")
    return 1e3 * seconds / calls if calls else None
