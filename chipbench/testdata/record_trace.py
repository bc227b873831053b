#!/usr/bin/env python3
"""Record the small trace that ``tests/test_trace_reduce.py`` reads.

    python3 chipbench/testdata/record_trace.py <out_dir>

On a TPU: a jitted ``small_step`` (a 1024 x 1024 bf16 product) runs five
times inside the span ``chipbench.window`` (10 ms of margin at each
end), each run in a ``chipbench.step`` span and followed by a 20 ms
``chipbench.wait``.
The ``.xplane.pb`` is copied to ``<out_dir>/small_trace.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

RUNS = 5
WAIT_S = 0.02
MARGIN_S = 0.01


@jax.jit
def small_step(x):
    return (x @ x).astype(jnp.float32).sum()


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    small_step(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        # the device's timestamps sit about a millisecond off the host's
        # in a TPU trace: margins keep every run inside the window
        time.sleep(MARGIN_S)
        for _ in range(RUNS):
            with jax.profiler.TraceAnnotation("chipbench.step"):
                small_step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                time.sleep(WAIT_S)
        time.sleep(MARGIN_S)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                           recursive=True))[-1]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(src, os.path.join(out_dir, "small_trace.xplane.pb"))
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
