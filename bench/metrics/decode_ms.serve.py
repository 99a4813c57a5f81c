"""Device time per greedy-decode call of the server, from the trace."""


def read(ctx):
    seconds, calls = ctx["trace"].module_time(r"greedy|exported|call")
    return 1e3 * seconds / calls if calls else None
