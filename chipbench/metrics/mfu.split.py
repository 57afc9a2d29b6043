"""The whole split step's share of the chip's bf16 peak, in %: the FLOPs
the traced rounds' steps require (the configuration's reference counts
them), over the traced slice's length and the peak."""


def read(ctx):
    tr = ctx.get("trace")
    flops = ctx.get("traced_required_flops")
    if not tr or not flops or not tr["window_s"]:
        return None
    return 100.0 * flops / tr["window_s"] / ctx["peak"]["bf16_flops_per_s"]
