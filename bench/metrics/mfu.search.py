"""Model operations of the traced windows over their time, as a share of
the chips' bf16 peak (operations counted by bench/flops.py)."""


def read(ctx):
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    if not ctx["windows"] or not peak > 0:
        return None
    return 100.0 * ctx["model_ops"] / ctx["window_s"] / peak
