#!/usr/bin/env python3
"""Record the small trace that ``tests/test_scopes.py`` reads.

    python3 chipbench/testdata/record_scoped_trace.py <out_dir>

On a TPU: a jitted ``scoped_step`` (a scan of four layers under
``layers``, each a 512 x 512 bf16 product under ``attention`` and a
512 x 2048 x 512 pair under ``ffn``) runs five times inside the span
``chipbench.window`` (10 ms of margin at each end).  Each run is a
``chipbench.step`` holding the program's own spans as the serving path
nests them: ``repro.scheduler.step`` (``width=3``, ``active=8``) around
``repro.engine.decode`` (the dispatch), ``repro.adapter.readback`` (the
wait) and a 5 ms host-only ``repro.adapter.accept``; then a
``chipbench.admit`` holding ``repro.scheduler.admit`` (``rids``) around
``repro.engine.prefill`` (``computed_tokens=5``, ``padded_tokens=64``,
``bucket=8``, a 2 ms sleep), and a 20 ms ``chipbench.wait``.
The ``.xplane.pb`` is copied to ``<out_dir>/scoped_trace.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

RUNS = 5
LAYERS = 4
ACCEPT_S = 0.005
PREFILL_S = 0.002
WAIT_S = 0.02
MARGIN_S = 0.01


@jax.jit
def scoped_step(x, w_in, w_out):
    def layer(h, _):
        with jax.named_scope("attention"):
            h = h + (h @ h.T).astype(h.dtype) @ h / h.shape[0]
        with jax.named_scope("ffn"):
            h = h + jnp.tanh(h @ w_in) @ w_out
        return h, None

    with jax.named_scope("layers"):
        h, _ = jax.lax.scan(layer, x, None, length=LAYERS)
    return h.astype(jnp.float32).sum()


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: no TPU", file=sys.stderr)
        return 2
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (512, 512), jnp.bfloat16) * 0.1
    w_in = jax.random.normal(k[1], (512, 2048), jnp.bfloat16) * 0.02
    w_out = jax.random.normal(k[2], (2048, 512), jnp.bfloat16) * 0.02
    scoped_step(x, w_in, w_out).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with TraceAnnotation("chipbench.window"):
        time.sleep(MARGIN_S)
        for i in range(RUNS):
            with TraceAnnotation("chipbench.step"), \
                    TraceAnnotation("repro.scheduler.step", width=3,
                                    active=8):
                with TraceAnnotation("repro.engine.decode"):
                    out = scoped_step(x, w_in, w_out)
                with TraceAnnotation("repro.adapter.readback"):
                    out.block_until_ready()
                with TraceAnnotation("repro.adapter.accept"):
                    time.sleep(ACCEPT_S)
            with TraceAnnotation("chipbench.admit"), \
                    TraceAnnotation("repro.scheduler.admit", rids=f"{i} 9"):
                with TraceAnnotation("repro.engine.prefill",
                                     computed_tokens=5, padded_tokens=64,
                                     bucket=8):
                    time.sleep(PREFILL_S)
            with TraceAnnotation("chipbench.wait"):
                time.sleep(WAIT_S)
        time.sleep(MARGIN_S)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                           recursive=True))[-1]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(src, os.path.join(out_dir, "scoped_trace.xplane.pb"))
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
