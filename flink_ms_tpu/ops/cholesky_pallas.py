"""Fused batched SPD solve as a Pallas TPU kernel.

The ALS half-sweep ends in n independent k×k normal-equation solves
(k = numFactors, 10-128; n = entities per block, 10^4-10^6).  Both XLA's
``lax.linalg.cholesky`` (a while-loop of dynamic slices — latency-bound)
and the unrolled rank-1-downdate formulation (streams the whole (n, k, k)
tensor from HBM once per elimination step — ~n·k³ bytes of traffic) are
memory-bound on TPU.  The roofline optimum is to read A once and write x
once; that needs the factorization to stay resident, which is exactly a
Pallas kernel:

- **batch on the lane axis**: tiles are laid out (k, k, T) with T batch
  elements on the 128-wide lane dimension, so every elimination step is a
  (k, T) vectorized VPU op — no per-element scalar loops;
- the k-step Cholesky, forward- and back-substitution all run on the tile
  while it lives in VMEM; HBM sees one read of A/b and one write of x.

Two ways in.  ``cholesky_solve_batched`` takes A ``(n, k, k)`` as the einsum
paths assemble it and lays it out for the kernel in XLA (a transpose and a
pad over the whole tensor) or, ``layout="batch_major"``, per tile in VMEM.
``cholesky_solve_lanes`` takes At ``(k, k, n)`` as the assembly kernel
writes it (``assemble_pallas.assemble_bucket_lanes``) and the
regularisation as a per-lane operand that the kernel adds to the diagonal
in VMEM, so that A is read from HBM once and nothing rewrites it on the
way: at the ML-20M shape 7.2 ms an iteration in ``als.solve`` where the
reg add, the pad and the kernel took 18.3 (PERF.md section 5, PR 30).

One rule, ``solver_tile``, gives every entry its tile and the VMEM it asks
for.  Up to rank 64 that is the default scoped 16 MB (the ranks the cells
``als-ml20m.retrain``, 50, and ``msd-ials.ials-retrain``, 64, time); from 65
to 128 the kernel names its own limit, because one lane tile of systems no
longer fits the default: at rank 100 the lane-major entries need 31 MiB and
the batch-major one 21 MiB (what the v5e compiler reports, PERF.md section
3, PR 44; ``netflix-als-f100.retrain`` times that rank).  The elimination is
unrolled over the static k, so the kernel's trace and lowering grow with k
squared: the price of a start, not of an iteration.

The caller says where it runs: ``interpret=True`` is the interpreter-mode
path CPU tests pin numerics with, ``interpret=False`` compiles for the TPU.
``ops/als._chol_solve`` derives it from its mesh's platform; selection of
this solver is ``resolve_solver`` (default on TPU, FLINK_MS_ALS_SOLVER).

Reference capability: the per-ID regularized solves inside FlinkML's
blocked ALS [dep], reached from ``ALSImpl.scala:52`` (SURVEY.md §2.2).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # one lane tile: the systems a grid step solves side by side


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def solver_tile(k: int, layout: str) -> Tuple[int, Optional[int]]:
    """-> (tile, vmem_limit_bytes) of a solver entry at rank ``k``: the
    systems per grid step, and the scoped VMEM the kernel asks Mosaic for
    (None: the default 16 MB).  ``layout`` is "lane_major"
    (``cholesky_solve_lanes`` and ``cholesky_solve_batched``'s default, A
    arriving as (k, k, tile) blocks) or "batch_major" (A arriving as
    (tile, k, k) blocks and transposed in VMEM).

    Up to rank 64 every answer is the one the cells' programs were compiled
    and timed with: a whole lane tile under the default limit, except the
    batch-major entry from rank 57, which takes half a tile (its nine
    k x k x tile buffers measured 18.87 MB at k = 64 against the 16 MB limit
    on the installation it was written on).

    Above 64 a lane tile no longer fits the default, and a smaller tile
    does not help: in VMEM the systems lie on the lanes, and a tile of 32 or
    64 occupies the same whole 128-lane tiles (the batch-major kernel needs
    21 MiB at k = 100 with a tile of 128, 64 and 32 alike) and leaves VPU
    lanes idle.  So the tile stays whole and the kernel asks for what it
    needs, counted as eight buffers of k rows (padded to 8 sublanes) x k x
    128 lanes of f32: the input block twice (double-buffered), the
    downdated copy, the column and the row stack, and headroom.  The least
    limit the v5e compiler accepts, bisected to the MiB (a described chip,
    PR 44): lane-major 13 MiB at k = 64 (6.5 buffers), 31 at 100 (6.1), 50
    at 128 (6.3); batch-major 9, 21 and 47.  The rule asks for 43 MB at
    k = 100 and 67 at k = 128, of the v5e's 128 MiB of VMEM: a ceiling, not
    an allocation."""
    if k <= 64:
        halve = (layout == "batch_major"
                 and 9 * k * k * LANES * 4 > 14 * (1 << 20))
        return (LANES // 2 if halve else LANES), None
    return LANES, 8 * _round_up(k, 8) * k * LANES * 4


def _compiler_params(vmem_limit: Optional[int]) -> dict:
    """``pallas_call``'s keyword for a kernel that names its VMEM limit;
    nothing for one that does not, so that its lowered program is the one
    without the parameter."""
    if vmem_limit is None:
        return {}
    return {"compiler_params":
            pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit))}


def _solve_tile(M, b, k: int):
    """A (k, k, T) SPD, b (k, T) -> x (k, T), T systems on the lanes.

    Right-looking Cholesky by rank-1 downdates, then the two triangular
    substitutions, fully unrolled over the static k — every op is
    vectorized over the T lanes.
    """
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)
    cols = []                                     # cols[j]: (k, T), >=2D ops
    for j in range(k):
        d = jax.lax.rsqrt(M[j, j:j + 1, :])       # (1, T)
        col = M[:, j, :] * d                      # (k, T)
        col = jnp.where(rows >= j, col, 0.0)      # zero rows above the pivot
        cols.append(col)
        M = M - col[:, None, :] * col[None, :, :]
    # L[i, j] = cols[j][i]; diag entries as a (k, T) stack for the solves
    diag = jnp.concatenate([c[j:j + 1, :] for j, c in enumerate(cols)], axis=0)

    # forward solve L z = b with a running accumulator acc = Σ_p L[:,p]·z_p
    acc = jnp.zeros_like(b)
    zs = []                                       # zs[j]: (1, T)
    for j in range(k):
        z = (b[j:j + 1, :] - acc[j:j + 1, :]) / diag[j:j + 1, :]
        zs.append(z)
        acc = acc + cols[j] * z
    # back solve Lᵀ x = z: after fixing x_j, fold row j of L (gathered
    # from the column stack: L[j, p] = cols[p][j]) into acc
    Lrows = jnp.stack([c for c in cols], axis=1)  # (k, k, T): [i, j, :]
    acc = jnp.zeros_like(b)
    xs = [None] * k
    for j in reversed(range(k)):
        x = (zs[j] - acc[j:j + 1, :]) / diag[j:j + 1, :]
        xs[j] = x
        acc = acc + Lrows[j, :, :] * x            # row j of L, (k, T)
    return jnp.concatenate(xs, axis=0)            # (k, T)


def _solve_kernel(a_ref, b_ref, *rest, k: int):
    """One tile: A (k, k, T), b (k, T) [, d (1, T)] -> x (k, T).  With d the
    system solved is A + d·I: the regularisation reaches the diagonal here,
    in VMEM, and no pass over A in HBM adds it."""
    *d_ref, x_ref = rest
    M = a_ref[:]
    if d_ref:
        on_diag = (jax.lax.broadcasted_iota(jnp.int32, (k, k, 1), 0)
                   == jax.lax.broadcasted_iota(jnp.int32, (k, k, 1), 1))
        M = M + jnp.where(on_diag, d_ref[0][:][None], 0.0)
    x_ref[:] = _solve_tile(M, b_ref[:], k)


@functools.partial(jax.jit,
                   static_argnames=("tile", "interpret", "vmem_limit"))
def _solve_padded(At, bt, tile: int, interpret: bool, d=None,
                  vmem_limit: Optional[int] = None):
    k = At.shape[0]
    n_pad = At.shape[2]
    lanes = pl.BlockSpec((k, tile), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_solve_kernel, k=k),
        grid=(n_pad // tile,),
        in_specs=[pl.BlockSpec((k, k, tile), lambda i: (0, 0, i)), lanes]
        + ([] if d is None else [pl.BlockSpec((1, tile), lambda i: (0, i))]),
        out_specs=lanes,
        out_shape=jax.ShapeDtypeStruct((k, n_pad), At.dtype),
        interpret=interpret,
        **_compiler_params(vmem_limit),
    )(At, bt, *(() if d is None else (d,)))


def _solve_kernel_batch_major(a_ref, b_ref, x_ref, *, k: int):
    """Batch-major tile: A (T, k, k), b (T, k) -> x (T, k).  The lane-major
    transpose happens INSIDE the kernel (VMEM-resident vector shuffles),
    so XLA never lays out a lane-major operand for the whole array —
    inside a lax.map/scan body that layout materialized as a degenerate-
    dim copy lane-padded x128 (62.5 GB for a (43648, 50, 50) chunk, the
    round-3 fused-mode AOT OOM)."""
    M = jnp.transpose(a_ref[:], (1, 2, 0))        # (k, k, T) in VMEM
    b = jnp.transpose(b_ref[:], (1, 0))           # (k, T)
    x_ref[:] = jnp.transpose(_solve_tile(M, b, k), (1, 0))


@functools.partial(jax.jit,
                   static_argnames=("tile", "interpret", "vmem_limit"))
def _solve_padded_batch_major(Ab, bb, tile: int, interpret: bool,
                              vmem_limit: Optional[int] = None):
    n_pad, k = bb.shape
    kernel = functools.partial(_solve_kernel_batch_major, k=k)
    return pl.pallas_call(
        kernel,
        grid=(n_pad // tile,),
        in_specs=[
            pl.BlockSpec((tile, k, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, k), Ab.dtype),
        interpret=interpret,
        **_compiler_params(vmem_limit),
    )(Ab, bb)


def cholesky_solve_lanes(At, bt, d, *, interpret: bool):
    """(A + d·I) x = b for systems that already lie batch-minor, as
    ``assemble_pallas.assemble_bucket_lanes`` writes them: At (k, k, n),
    bt (k, n), d (n,) -> x (k, n), n a multiple of the lane tile.  A pad lane
    carries A = 0, b = 0, d = 1: the identity system, x = 0."""
    tile, vmem_limit = solver_tile(At.shape[0], "lane_major")
    return _solve_padded(At, bt, tile, bool(interpret), d[None, :],
                         vmem_limit=vmem_limit)


def cholesky_solve_batched(A, b, *, interpret: bool, layout="lane_major"):
    """Batched SPD solve A x = b.  A (n, k, k), b (n, k) -> x (n, k).

    ``solver_tile`` batch elements ride the lane axis per grid step.
    ``interpret`` comes from the platform of the
    caller's mesh, never from the process's default backend: a host-side
    fit in a process that also holds a chip must still interpret.

    ``layout``: "lane_major" transposes A/b to (k, k, n)/(k, n) at the
    XLA level before the kernel; "batch_major" feeds (n, k, k) blocks
    directly and transposes per tile inside VMEM.  "lane_major" is the
    default — chip-measured 62.7 vs 68.3
    ms/iter at 5M nnz / k=50 (the in-kernel transpose costs ~9%).  The
    fused assembly+solve path passes "batch_major" explicitly: inside a
    lax.map body XLA materializes the whole-array lane-major relayout as
    a degenerate-dim copy lane-padded x128 (62.5 GB for a (43648, 50, 50)
    chunk — the round-3 fused-mode AOT OOM), which batch_major sidesteps
    by never asking XLA for that layout."""
    n, k = b.shape
    tile, vmem_limit = solver_tile(k, layout)
    n_pad = _round_up(max(n, tile), tile)
    if layout == "batch_major":
        Ab = A.astype(jnp.float32)
        bb = b.astype(jnp.float32)
        if n_pad != n:
            # pad batch rows with the identity system (x = b = 0):
            # rsqrt(0) on zero-padding would spread inf/nan through those
            # rows only, but keeping them finite is free
            pad = n_pad - n
            Ab = jnp.concatenate(
                [Ab, jnp.broadcast_to(jnp.eye(k, dtype=Ab.dtype),
                                      (pad, k, k))], axis=0)
            bb = jnp.pad(bb, ((0, pad), (0, 0)))
        return _solve_padded_batch_major(
            Ab, bb, tile, bool(interpret), vmem_limit=vmem_limit)[:n]
    At = jnp.transpose(A.astype(jnp.float32), (1, 2, 0))  # (k, k, n)
    bt = jnp.transpose(b.astype(jnp.float32), (1, 0))     # (k, n)
    if n_pad != n:
        # pad batch lanes with the identity system (x = b = 0)
        At = jnp.pad(At, ((0, 0), (0, 0), (0, n_pad - n)))
        eye_pad = jnp.eye(k, dtype=At.dtype)[:, :, None] * jnp.ones(
            (1, 1, n_pad - n), At.dtype
        )
        At = At.at[:, :, n:].set(eye_pad)
        bt = jnp.pad(bt, ((0, 0), (0, n_pad - n)))
    x = _solve_padded(At, bt, tile, bool(interpret), vmem_limit=vmem_limit)
    return jnp.transpose(x[:, :n], (1, 0))
