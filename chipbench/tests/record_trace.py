#!/usr/bin/env python3
"""Record the small profiler trace under ``chipbench/testdata/``.

    python chipbench/tests/record_trace.py OUT_DIR

Runs two small named jitted programs on the first device, with host pauses
between them inside host spans, under ``jax.profiler``; copies the
``.xplane.pb`` to ``OUT_DIR/small.xplane.pb`` and prints each plane's
lines with a few event names, so the layout can be read by hand.
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
    def matmul_chain(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    @jax.jit
    def elementwise(x):
        return jnp.exp(x) * 2.0 + 1.0

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    matmul_chain(x).block_until_ready()
    elementwise(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    window = jax.profiler.TraceAnnotation("bench.traced_window")
    window.__enter__()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.matmul"):
            matmul_chain(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host_pause"):
            time.sleep(0.002)
        with jax.profiler.TraceAnnotation("bench.elementwise"):
            elementwise(x).block_until_ready()
    window.__exit__(None, None, None)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(tmp)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(os.path.join(out, "small.xplane.pb"))
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:6]:
                stats = {k: v for k, v in list(ev.stats)[:8]}
                print("     ", repr(ev.name), ev.start_ns, ev.duration_ns,
                      stats)
    print(jax.devices()[0].memory_stats())


if __name__ == "__main__":
    main()
