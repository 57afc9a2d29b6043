#!/usr/bin/env python3
"""Record the stage-attribution test trace under ``chipbench/testdata/``.

    python chipbench/tests/record_spans_trace.py OUT_DIR

Runs a small jitted program whose loop sits in one named scope,
``sl.loop``, and is differentiated, so the loop's ops are found forward
and, inside ``transpose(...)``, backward; the loop's body ops run inside
the loop's own op event. Each execution is dispatched inside an ``sl.``
span, with a host pause in another ``sl.`` span, all inside the benchmark's
``bench.traced_window``. Writes ``OUT_DIR/spans.xplane.pb`` and the
program's compiled text, ``OUT_DIR/spans.hlo.txt``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main():
    out = sys.argv[1]
    os.makedirs(out, exist_ok=True)

    @jax.jit
    def step(w, x):
        def loss(w):
            with jax.named_scope("sl.loop"):
                y = jax.lax.fori_loop(0, 4, lambda i, y: jnp.tanh(y @ w), x)
            return jnp.sum(y.astype(jnp.float32))
        return jax.value_and_grad(loss)(w)

    w = jnp.full((512, 512), 0.01, jnp.bfloat16)
    x = jnp.ones((512, 512), jnp.bfloat16)
    jax.block_until_ready(step(w, x))
    with open(os.path.join(out, "spans.hlo.txt"), "w") as f:
        f.write(step.lower(w, x).compile().as_text())
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("sl.step"):
                jax.block_until_ready(step(w, x))
            with jax.profiler.TraceAnnotation("sl.host_pause"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, os.path.join(out, "spans.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
