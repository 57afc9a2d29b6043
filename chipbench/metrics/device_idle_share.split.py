"""Share of the traced slice in which no program ran on the chip, in %."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["idle_share"] is None:
        return None
    return 100.0 * tr["idle_share"]
