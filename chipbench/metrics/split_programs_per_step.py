"""Device program executions in the traced slice per execution of the split
step: the step itself plus every small program the host dispatches eagerly
around it (optimizer, adapter split and merge), each of which can leave the
chip idle while the host dispatches the next."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    progs = tr["programs"]
    steps = sum(p["count"] for name, p in progs.items()
                if name.startswith("jit_split_grads"))
    if not steps:
        return None
    return sum(p["count"] for p in progs.values()) / steps
