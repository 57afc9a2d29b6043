"""Device time of one split step: the ``jit_split_grads`` program's
device seconds in the traced slice over its executions, in ms."""
from chipbench.trace import program_seconds


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    secs, count = program_seconds(tr, "jit_split_grads")
    return 1e3 * secs / count if count else None
