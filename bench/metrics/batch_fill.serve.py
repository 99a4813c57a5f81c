"""Requests per flushed batch over the batch's slots, from the server's
counters over the window."""


def read(ctx):
    if not ctx["batches"]:
        return None
    return 100.0 * ctx["served"] / ctx["batches"] / ctx["batch_slots"]
