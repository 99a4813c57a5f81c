"""Share of the device's busy time in which a collective (the replay's
gradient reduction over the chips) ran and no other operation did."""

COLLECTIVES = r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"


def read(ctx):
    if not ctx["busy_s"]:
        return None
    exposed = ctx["trace"].exposed_s(COLLECTIVES)
    if not exposed:
        return None
    return 100.0 * exposed / ctx["busy_s"]
