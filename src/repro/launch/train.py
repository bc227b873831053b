"""Production training launcher: mesh + sharding + data + checkpoints +
restart-on-failure.

Single-host CPU demo:
  PYTHONPATH=src python -m repro.launch.train --arch stablelm_3b --tiny \
      --steps 50

On a real fleet each host runs this same script under
`jax.distributed.initialize()` (see --coordinator); the mesh spans all
processes, the data pipeline shards by process_index, and a host failure
is handled by the launcher's restore-and-resume path (dist.elastic).
"""
from __future__ import annotations

import argparse
import functools
import time
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.checkpoint import AsyncCheckpointer, latest_step, restore
from repro.configs import get_config
from repro.data import DataConfig, make_pipeline
from repro.dist.elastic import StepWatchdog, elastic_mesh, run_with_restarts
from repro.dist.sharding import (batch_pspec, opt_pspecs, param_pspecs,
                                 shardings_from_pspecs)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import init_model
from repro.training import AdamWConfig, init_opt_state, make_train_step


def init_train_state(key, cfg) -> Dict:
    params = init_model(key, cfg)
    return {"params": params, "opt": init_opt_state(params)}


def sharded_train(cfg, mesh: Mesh, opt_cfg: AdamWConfig, *,
                  global_batch: int, n_micro: int, policy: str = "auto",
                  ) -> Tuple[Callable, Callable]:
    """Jitted ``(init, step)`` over ``mesh``.

    ``init(key)`` creates params and optimizer state under their
    shardings, so no chip ever holds the whole state (at stablelm_3b
    width the f32 Adam state alone is ~22 GB).  ``step(state, batch)``
    returns ``(state, metrics)`` and donates the state it replaces."""
    init = functools.partial(init_train_state, cfg=cfg)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    p_ps = param_pspecs(shapes["params"], mesh, policy=policy)
    state_sh = {"params": shardings_from_pspecs(p_ps, mesh),
                "opt": shardings_from_pspecs(
                    opt_pspecs(shapes["opt"], p_ps, mesh), mesh)}
    batch_sh = shardings_from_pspecs(
        {"tokens": batch_pspec(mesh, global_batch)}, mesh)
    train_step = make_train_step(cfg, opt_cfg, n_micro=n_micro)

    def step(state, batch):
        params, opt, metrics = train_step(state["params"], state["opt"],
                                          batch)
        return {"params": params, "opt": opt}, metrics

    return (jax.jit(init, out_shardings=state_sh),
            jax.jit(step, in_shardings=(state_sh, batch_sh),
                    out_shardings=(state_sh, None), donate_argnums=0))


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_3b")
    ap.add_argument("--tiny", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-micro", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-path", default=None,
                    help="binary shard dir; default synthetic")
    ap.add_argument("--coordinator", default=None,
                    help="host:port for jax.distributed (multi-host)")
    ap.add_argument("--sharding-policy", default="auto",
                    choices=["auto", "fsdp", "tp_only", "dp_only"])
    args = ap.parse_args()

    if args.coordinator:
        jax.distributed.initialize(coordinator_address=args.coordinator)

    cfg = get_config(args.arch, reduced=args.tiny)
    n_dev = jax.device_count()
    shape, axes = elastic_mesh(n_dev)
    mesh = make_mesh(shape, axes)
    print(f"mesh {dict(zip(axes, shape)) if n_dev > 1 else '1-device'}  "
          f"arch {cfg.name}")

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                          total_steps=args.steps)
    init_fn, step_fn = sharded_train(
        cfg, mesh, opt_cfg, global_batch=args.global_batch,
        n_micro=args.n_micro, policy=args.sharding_policy)

    data = make_pipeline(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                   global_batch=args.global_batch, path=args.data_path),
        process_index=jax.process_index(),
        num_processes=jax.process_count())
    ckpt = AsyncCheckpointer(args.ckpt_dir, keep=3)
    watchdog = StepWatchdog(deadline_s=600.0)

    state = init_fn(jax.random.PRNGKey(0))
    start = 0
    if latest_step(args.ckpt_dir) is not None:
        restored, meta = restore(args.ckpt_dir, state)
        state = restored
        start = int(meta.get("step", 0))
        print(f"resumed at step {start}")

    def one_step(step: int) -> None:
        t0 = time.time()
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        new_state, metrics = step_fn(state, batch)
        state.update(new_state)
        dt = time.time() - t0
        watchdog.observe(dt)
        if step % 10 == 0:
            print(f"step {step:5d}  loss={float(metrics['loss']):.4f}  "
                  f"lr={float(metrics['lr']):.2e}  {dt:.2f}s")
        if step and step % args.ckpt_every == 0:
            ckpt.save(step, state, {"step": step})

    def restore_fn() -> int:
        restored, meta = restore(args.ckpt_dir, state)
        state.update(restored)
        return int(meta.get("step", 0))

    run_with_restarts(one_step, start, args.steps, restore_fn)
    ckpt.save(args.steps, state, {"step": args.steps})
    ckpt.wait()
    print("training complete; checkpoint committed")


if __name__ == "__main__":
    main()
