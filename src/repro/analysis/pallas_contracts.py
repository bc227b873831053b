"""Checker 3: Pallas kernel launch contracts, via dry-run capture.

``pl.pallas_call`` is monkeypatched with a recorder and each kernel's
ops-level entry is invoked on tiny representative decode-regime inputs
under ``jax.disable_jit()`` — so every operand, grid, BlockSpec and
scalar-prefetch VALUE is concrete without compiling or running any
kernel.  The captured launches then get checked statically:

  PK001  operand arity != num_scalar_prefetch + len(in_specs)
  PK002  kernel fn positional-parameter count != prefetch + inputs +
         outputs + scratch (skipped for *args kernels)
  PK003  a BlockSpec index map raises or returns the wrong rank
  PK004  an index map returns an OUT-OF-BOUNDS block index somewhere on
         the launch grid (evaluated per grid point with the real
         prefetch values — this is how a bad clamp in the ragged
         tile-skip map or a corrupt block-table entry surfaces)
  PK005  a block shape does not divide its operand dimension (silent
         partial edge tiles)

The same captures feed the granularity-drift checker: the block shapes
kernels ACTUALLY launch with are compared against what
``core.granularity`` declares (see ``granularity_drift``).
"""
from __future__ import annotations

import inspect
import itertools
import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from repro.analysis.findings import Finding

CHECKER = "pallas-contract"

KERNEL_PATHS = {
    "decode_attention": "src/repro/kernels/decode_attention/kernel.py",
    "moe_ffn": "src/repro/kernels/moe_ffn/kernel.py",
    "mamba_scan": "src/repro/kernels/mamba_scan/kernel.py",
}


@dataclass
class CapturedLaunch:
    label: str                      # "decode_attention_ragged/n1", ...
    kernel_path: str                # repo-relative kernel source path
    kernel_name: str
    grid: Tuple[int, ...]
    num_scalar_prefetch: int
    in_specs: List[Any]             # pl.BlockSpec
    out_specs: List[Any]
    in_shapes: List[Tuple[int, ...]]
    out_shapes: List[Tuple[int, ...]]
    prefetch_values: List[Any]      # concrete numpy arrays
    kernel_params: Optional[int]    # positional count, None for *args
    scratch_count: int = 0
    line: int = 1


@dataclass
class CaptureTarget:
    label: str
    kernel_path: str
    run: Callable[[], None] = field(repr=False, default=None)


def _specs_list(specs) -> List[Any]:
    if specs is None:
        return []
    if isinstance(specs, (list, tuple)):
        return list(specs)
    return [specs]


def capture_launches(targets: Optional[Sequence[CaptureTarget]] = None
                     ) -> List[CapturedLaunch]:
    """Run the capture targets with ``pl.pallas_call`` replaced by a
    recorder; returns one CapturedLaunch per pallas_call invocation."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    if targets is None:
        targets = default_targets()
    captured: List[CapturedLaunch] = []
    current: Dict[str, str] = {"label": "", "path": ""}
    real = pl.pallas_call

    def fake_pallas_call(kernel, out_shape=None, *, grid_spec=None,
                         grid=(), in_specs=None, out_specs=None,
                         scratch_shapes=(), **kw):
        if grid_spec is not None:
            grid_ = tuple(grid_spec.grid)
            in_specs_ = _specs_list(grid_spec.in_specs)
            out_specs_ = _specs_list(grid_spec.out_specs)
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
            scratch = _specs_list(grid_spec.scratch_shapes)
        else:
            grid_ = tuple(grid) if isinstance(grid, (list, tuple)) else (grid,)
            in_specs_ = _specs_list(in_specs)
            out_specs_ = _specs_list(out_specs)
            nsp = 0
            scratch = _specs_list(scratch_shapes)
        out_structs = (list(out_shape) if isinstance(out_shape, (list, tuple))
                       else [out_shape])

        fn = kernel
        while hasattr(fn, "func"):        # unwrap functools.partial chains
            fn = fn.func
        try:
            sig_params = [p for p in inspect.signature(kernel).parameters
                          .values()]
            if any(p.kind == p.VAR_POSITIONAL for p in sig_params):
                n_params: Optional[int] = None
            else:
                n_params = sum(p.kind in (p.POSITIONAL_ONLY,
                                          p.POSITIONAL_OR_KEYWORD)
                               for p in sig_params)
        except (TypeError, ValueError):
            n_params = None

        def runner(*operands):
            captured.append(CapturedLaunch(
                label=current["label"],
                kernel_path=current["path"],
                kernel_name=getattr(fn, "__name__", str(fn)),
                grid=grid_,
                num_scalar_prefetch=nsp,
                in_specs=in_specs_,
                out_specs=out_specs_,
                in_shapes=[tuple(np.shape(o)) for o in operands[nsp:]],
                out_shapes=[tuple(s.shape) for s in out_structs],
                prefetch_values=[np.asarray(o) for o in operands[:nsp]],
                kernel_params=n_params,
                scratch_count=len(scratch),
            ))
            outs = [jnp.zeros(s.shape, s.dtype) for s in out_structs]
            return outs if isinstance(out_shape, (list, tuple)) else outs[0]
        return runner

    pl.pallas_call = fake_pallas_call
    try:
        with jax.disable_jit():
            for t in targets:
                current["label"], current["path"] = t.label, t.kernel_path
                t.run()
    finally:
        pl.pallas_call = real
    return captured


# ---------------------------------------------------------------------------
# representative decode-regime examples — small enough to run eagerly on
# any host, shaped to exercise multi-tile grids and the ragged clamps
# ---------------------------------------------------------------------------

def default_targets() -> List[CaptureTarget]:
    import jax.numpy as jnp

    kp = KERNEL_PATHS

    def ragged(n: int, window=None):
        def run():
            from repro.kernels.decode_attention import ops
            b, s, h, kv, dh = 2, 256, 4, 2, 128
            q = jnp.zeros((b, n, h, dh), jnp.float32)
            k = jnp.zeros((b, s, kv, dh), jnp.float32)
            v = jnp.zeros((b, s, kv, dh), jnp.float32)
            lens = jnp.asarray([0, 130], jnp.int32)   # row 1 spans 2 kv tiles
            ops.decode_attention_ragged(q, k, v, lens, window=window)
        return run

    def paged():
        from repro.kernels.decode_attention import ops
        n_phys, bs, kv, dh, b = 6, 16, 2, 128, 2
        q = jnp.zeros((b, 1, 4, dh), jnp.float32)
        kpool = jnp.zeros((2, kv, n_phys, dh, bs), jnp.float32)
        vpool = jnp.zeros((2, kv, n_phys, dh, bs), jnp.float32)
        lens = jnp.asarray([5, 30], jnp.int32)
        tables = jnp.asarray([[0, 1, 5, 5], [2, 3, 4, 5]], jnp.int32)
        ops.decode_attention_paged(q, kpool, vpool, lens, tables, 1)

    def paged_write():
        from repro.kernels.decode_attention import ops
        n_phys, bs, kv, dh, b, n = 6, 16, 2, 128, 2, 3
        kpool = jnp.zeros((2, kv, n_phys, dh, bs), jnp.float32)
        vpool = jnp.zeros((2, kv, n_phys, dh, bs), jnp.float32)
        new = jnp.zeros((b, n, kv, dh), jnp.float32)
        pages = jnp.asarray([[1, 5], [3, 4]], jnp.int32)  # row 1 straddles
        starts = jnp.asarray([2, 14], jnp.int32)
        ops.paged_kv_write(kpool, vpool, new, new, 1, pages, starts)

    def moe():
        from repro.kernels.moe_ffn import ops
        e, d, f, m = 8, 64, 512, 2
        params = {
            "w_gate": jnp.zeros((e, d, f), jnp.float32),
            "w_up": jnp.zeros((e, d, f), jnp.float32),
            "w_down": jnp.zeros((e, f, d), jnp.float32),
        }
        x = jnp.zeros((m, d), jnp.float32)
        sizes = jnp.asarray([1, 1, 0, 0, 0, 0, 0, 0], jnp.int32)
        ops.grouped_ffn(x, params, sizes, "swiglu", n_tokens=1)

    def scan():
        from repro.kernels.mamba_scan import ops
        b, s, di, ds = 1, 5, 8, 4
        x = jnp.zeros((b, s, di), jnp.float32)
        dt = jnp.zeros((b, s, di), jnp.float32)
        bi = jnp.zeros((b, s, ds), jnp.float32)
        ci = jnp.zeros((b, s, ds), jnp.float32)
        a = jnp.zeros((di, ds), jnp.float32)
        h0 = jnp.zeros((b, di, ds), jnp.float32)
        ops.selective_scan(x, dt, bi, ci, a, h0)

    return [
        CaptureTarget("decode_attention_ragged/n1", kp["decode_attention"],
                      ragged(1)),
        CaptureTarget("decode_attention_ragged/n65", kp["decode_attention"],
                      ragged(65)),
        CaptureTarget("decode_attention_ragged/n1_window",
                      kp["decode_attention"], ragged(1, window=64)),
        CaptureTarget("decode_attention_paged/n1", kp["decode_attention"],
                      paged),
        CaptureTarget("paged_kv_write/n3", kp["decode_attention"],
                      paged_write),
        CaptureTarget("grouped_ffn/decode", kp["moe_ffn"], moe),
        CaptureTarget("selective_scan/decode", kp["mamba_scan"], scan),
    ]


# ---------------------------------------------------------------------------
# static checks over captured launches
# ---------------------------------------------------------------------------

MAX_GRID_POINTS = 8192


def _grid_points(grid: Tuple[int, ...]):
    total = math.prod(grid) if grid else 0
    pts = itertools.product(*(range(g) for g in grid))
    return itertools.islice(pts, MAX_GRID_POINTS), total


def check_launch(launch: CapturedLaunch) -> List[Finding]:
    out: List[Finding] = []

    def emit(rule: str, message: str) -> None:
        out.append(Finding(CHECKER, rule, launch.kernel_path, launch.line,
                           f"{launch.kernel_name}[{launch.label}]", message,
                           snippet=f"grid={launch.grid}"))

    nsp = launch.num_scalar_prefetch
    n_in, n_out = len(launch.in_shapes), len(launch.out_shapes)
    if len(launch.in_specs) != n_in:
        emit("PK001",
             f"{n_in} array operands but {len(launch.in_specs)} in_specs "
             f"(num_scalar_prefetch={nsp}): prefetch/operand arity drift")
        return out
    if launch.kernel_params is not None:
        want = nsp + n_in + n_out + launch.scratch_count
        if launch.kernel_params != want:
            emit("PK002",
                 f"kernel takes {launch.kernel_params} positional refs but "
                 f"the launch supplies {want} ({nsp} prefetch + {n_in} in "
                 f"+ {n_out} out + {launch.scratch_count} scratch)")

    pairs = (list(zip(launch.in_specs, launch.in_shapes))
             + list(zip(launch.out_specs, launch.out_shapes)))
    roles = ([f"in_specs[{i}]" for i in range(n_in)]
             + [f"out_specs[{i}]" for i in range(n_out)])
    points, total = None, 0
    for role, (spec, shape) in zip(roles, pairs):
        block = tuple(int(b) for b in (spec.block_shape or shape))
        if len(block) != len(shape):
            emit("PK003", f"{role}: block rank {len(block)} != operand "
                          f"rank {len(shape)} for shape {shape}")
            continue
        for d, (dim, blk) in enumerate(zip(shape, block)):
            if blk <= 0 or dim % blk:
                emit("PK005",
                     f"{role}: block {block} does not divide operand "
                     f"shape {shape} (dim {d}: {dim} % {blk} != 0) — "
                     "silent partial edge tile")
        index_map = spec.index_map
        if index_map is None:
            continue
        bounds = [max(1, -(-dim // blk)) for dim, blk in zip(shape, block)
                  if blk > 0] if all(b > 0 for b in block) else None
        if bounds is None:
            continue
        points, total = _grid_points(launch.grid)
        checked = 0
        for pt in points:
            try:
                idx = index_map(*pt, *launch.prefetch_values)
            except Exception as exc:  # wrong arity, bad prefetch indexing
                emit("PK003",
                     f"{role}: index map raised {type(exc).__name__} at "
                     f"grid point {pt}: {exc}")
                break
            if not isinstance(idx, (tuple, list)):
                idx = (idx,)
            if len(idx) != len(shape):
                emit("PK003",
                     f"{role}: index map returned {len(idx)} indices for "
                     f"rank-{len(shape)} operand at grid point {pt}")
                break
            bad = None
            for d, v in enumerate(idx):
                try:
                    vi = int(v)
                except Exception:
                    emit("PK003",
                         f"{role}: index map returned non-integer "
                         f"component {d} at grid point {pt}")
                    bad = "type"
                    break
                if not (0 <= vi < bounds[d]):
                    emit("PK004",
                         f"{role}: block index {vi} out of bounds "
                         f"[0, {bounds[d]}) in dim {d} at grid point "
                         f"{pt} (operand {shape}, block {block}) — "
                         "the DMA would read past the buffer")
                    bad = "oob"
                    break
            if bad:
                break
            checked += 1
        if total > MAX_GRID_POINTS and checked == MAX_GRID_POINTS:
            # sampled; note it rather than silently under-covering
            emit("PK003",
                 f"{role}: grid has {total} points, only first "
                 f"{MAX_GRID_POINTS} evaluated — shrink the capture "
                 "example")
    return out


def check(project=None, roots=None,
          captures: Optional[List[CapturedLaunch]] = None) -> List[Finding]:
    del project, roots
    if captures is None:
        captures = capture_launches()
    out: List[Finding] = []
    for launch in captures:
        out.extend(check_launch(launch))
    return out
