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
for: a whole lane tile of systems at every rank, under the default scoped
16 MB up to rank 64 (the ranks the cells ``als-ml20m.retrain``, 50, and
``msd-ials.ials-retrain``, 64, time); from 65 to 128 the kernel names its
own limit, because a lane tile of systems no longer fits the default: at
rank 100 the lane-major entries need 31 MiB and the batch-major one 17 (what
the v5e compiler reports, PERF.md section 3, PR 46;
``netflix-als-f100.retrain`` times that rank).  The elimination is unrolled
over the static k and shrinks with the pivot, a sublane group of rows and
columns at a time (``_solve_tile``), so the body's trace and lowering grow
with k squared: the price of a start, not of an iteration, and the calls of a
side solved per chunk, one a bucket, pay the trace once between them
(``shared``, ``_solve_tile_shared``).  What an
iteration pays is not the downdates' arithmetic.  The working matrix is 450
to 1,300 vregs against 64 registers, and the compiler's schedule of the
lane-major body sends every downdated vreg and most outer products through
VMEM, one store a bundle: 43,500 stores in the 50,100 bundles of a tile at
rank 100.  The batch-major body stores a third of that and fills the
arithmetic slots instead, half of them with the sublane rotations, selects
and permutes that gather and transpose the block's rows
(``scripts/solver_bundles.py`` counts both without a chip; PERF.md section
6, PR 46).

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

from ..obs import metrics as obs_metrics

LANES = 128  # one lane tile: the systems a grid step solves side by side
SUBLANES = 8  # an f32 vreg's rows: the step by which the elimination shrinks


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def solver_tile(k: int, layout: str) -> Tuple[int, Optional[int]]:
    """-> (tile, vmem_limit_bytes) of a solver entry at rank ``k``: the
    systems per grid step, and the scoped VMEM the kernel asks Mosaic for
    (None: the default 16 MB).  ``layout`` is "lane_major"
    (``cholesky_solve_lanes`` and ``cholesky_solve_batched``'s default, A
    arriving as (k, k, tile) blocks) or "batch_major" (A arriving as
    (tile, k, k) blocks and transposed in VMEM).

    The tile is a whole lane tile at every rank and on every entry: in VMEM
    the systems lie on the lanes, so a tile of 32 or 64 occupies the same
    whole 128-lane vregs, does the same vector work for fewer systems and
    needs the same memory (the batch-major kernel at k = 100 compiled under
    the same limit with a tile of 128, 64 and 32, PR 44; at k = 64 half a
    tile read 131 ns a system on the chip where a whole one reads 79, PR
    46).  Up to rank 64 that fits Mosaic's default limit.  Above it the
    kernel asks for what it needs, counted as eight buffers of k rows
    (padded to 8 sublanes) x k x 128 lanes of f32: the input block twice
    (double-buffered), the working copy, the column and the row stack, and
    headroom.  The least limit the v5e compiler accepts, bisected to the MiB
    for this body (a described chip, PR 46), at k = 57, 64, 100 and 128:
    lane-major 11, 13, 31 and 50 MiB (6.1 to 6.5 buffers; as before the
    elimination shrank, the whole tile being live at the first step);
    batch-major 6, 7, 17 and 50 (3.4 buffers, 6.3 at k = 128; 9, 21 and 47
    at 64, 100 and 128 while one 3-D transpose laid the block out).  A
    batch-major call sits in a ``lax.map`` step between XLA's own passes,
    which keep some 7 MiB of scoped VMEM of their own around it: the whole
    tile with the 3-D transpose was refused there at k = 64 (16.55 MB asked
    of 16, ``msd-ials``, PR 46); this body's 7 fit.  The default must do up to
    rank 64: a kernel that names more than 16 MiB inside that step costs
    ``msd-ials``' item table its place in the chip's fast memory
    (``scripts/als_compiled_layout.py``, PR 46).  Both layouts get one
    answer above it, sized for the larger: 43 MB at k = 100 and 67 at
    k = 128, of the v5e's 128 MiB of VMEM, a ceiling and not an
    allocation."""
    if k <= 64:
        return LANES, None
    return LANES, 8 * _round_up(k, 8) * k * LANES * 4


def _compiler_params(vmem_limit: Optional[int]) -> dict:
    """``pallas_call``'s keyword for a kernel that names its VMEM limit;
    nothing for one that does not, so that its lowered program is the one
    without the parameter."""
    if vmem_limit is None:
        return {}
    return {"compiler_params":
            pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit))}


def _row(x, j: int):
    """x[j:j + 1] as the one ``slice`` an index expression ends in; ``_at(x,
    j, axis)`` likewise is x with ``axis`` indexed at j.  The index
    expression costs the tracer three times as much, and a step of
    ``_solve_tile`` has eleven: 1,100 of them in a trace of the body at rank
    100."""
    return jax.lax.slice_in_dim(x, j, j + 1, axis=0)


_at = functools.partial(jax.lax.index_in_dim, keepdims=False)


def _solve_tile(M, b, k: int):
    """A (k, k, T) SPD, b (k, T) -> x (k, T), T systems on the lanes.

    Right-looking Cholesky by rank-1 downdates, then the two triangular
    substitutions, fully unrolled over the static k — every op is
    vectorized over the T lanes.

    The downdate runs on the trailing block only: after every SUBLANES
    pivots the finished rows and columns leave the working matrix, so step
    j touches (k - o)² entries, o = j rounded down to a sublane group, and
    not k².  Above the pivot's group the full-tile form subtracted 0 from
    entries no later step reads; what is read sees the same operations in
    the same order, so x is the full-tile form's to the bit.  Mosaic drops
    a vreg nobody reads, so the compiled full-tile body already skipped
    those updates, and more: the chip's time is the same for both (PERF.md
    section 6, PR 46).  The trailing block is what the trace, the
    interpreter and each call's lowering are spared.

    The counter below is a Python statement: it counts traces of the body,
    not calls of the kernel.
    """
    obs_metrics.get_registry().counter(
        "tpums_als_solver_body_traces_total").inc()
    zero = jnp.zeros((), M.dtype)
    cols, diag = [], []         # cols[j]: (k, T), >=2D ops; diag[j]: (1, T)
    for j in range(k):
        jj = j % SUBLANES                         # the pivot inside its group
        if not jj:
            if j:
                # the leading axis is untiled and the second is cut at a
                # whole sublane tile: whole vregs leave, no relayout
                M = M[SUBLANES:, SUBLANES:, :]
            m = M.shape[0]                        # k - o
            rows = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
        d = jax.lax.rsqrt(_row(_at(M, jj, 0), jj))   # M[jj, jj:jj + 1, :]
        col = _at(M, jj, 1) * d                   # M[:, jj, :], (k - o, T)
        col = jnp.where(rows >= jj, col, 0.0)     # zero rows above the pivot
        M = M - jnp.expand_dims(col, 1) * jnp.expand_dims(col, 0)
        diag.append(_row(col, jj))                # L[j, j]
        # the substitutions read whole columns, zero above the pivot
        cols.append(col if m == k else jax.lax.pad(
            col, zero, ((k - m, 0, 0), (0, 0, 0))))

    # forward solve L z = b with a running accumulator acc = Σ_p L[:,p]·z_p
    acc = jnp.zeros_like(b)
    zs = []                                       # zs[j]: (1, T)
    for j in range(k):
        z = (_row(b, j) - _row(acc, j)) / diag[j]
        zs.append(z)
        acc = acc + cols[j] * z
    # back solve Lᵀ x = z: after fixing x_j, fold row j of L (gathered
    # from the column stack: L[j, p] = cols[p][j]) into acc
    Lrows = jnp.stack(cols, axis=1)               # (k, k, T): [i, j, :]
    acc = jnp.zeros_like(b)
    xs = [None] * k
    for j in reversed(range(k)):
        x = (zs[j] - _row(acc, j)) / diag[j]
        xs[j] = x
        acc = acc + _at(Lrows, j, 0) * x          # row j of L, (k, T)
    return jnp.concatenate(xs, axis=0)            # (k, T)


# The body behind a jit, for a caller that says ``shared``: a side solved per
# chunk has a ``pallas_call`` a bucket, batch-major where the bucket takes
# several steps and lane-major where it takes one (each its own padded batch,
# hence its own trace of the kernel function: 19 at ``netflix-als-f100``, 13
# at ``msd-ials``), and all of them hand the body the same (k, k, 128) and
# (k, 128) f32 on both layouts, so a process traces it once per (k, T, dtype)
# and the other calls reuse the jaxpr: a body costs 2.4 s to trace at rank 100
# and 1.9 at 64 on the chip's host, at every start, cache hit or not (PERF.md
# section 6, PR 50).  Pallas inlines the inner jaxpr when it lowers the
# kernel: the module Mosaic gets is the plain body's to the byte
# (``tests/test_cholesky_pallas.py`` holds that).  A materialised side makes
# one call and keeps the plain body: nothing to share, and the jitted body is
# traced a dozen interpreter frames deeper, which is not free on that host
# (where the tracer's calls then straddle a 16 KB chunk of CPython's frame
# stack, every crossing maps and unmaps one: 2.7 s on one rank-50 body at
# ``als-ml20m-bf16x``, same section).
_solve_tile_shared = jax.jit(_solve_tile, static_argnames=("k",))


def _solve_kernel(a_ref, b_ref, *rest, k: int, shared: bool = False):
    """One tile: A (k, k, T), b (k, T) [, d (1, T)] -> x (k, T).  With d the
    system solved is A + d·I: the regularisation reaches the diagonal here,
    in VMEM, and no pass over A in HBM adds it."""
    *d_ref, x_ref = rest
    M = a_ref[:]
    if d_ref:
        on_diag = (jax.lax.broadcasted_iota(jnp.int32, (k, k, 1), 0)
                   == jax.lax.broadcasted_iota(jnp.int32, (k, k, 1), 1))
        M = M + jnp.where(on_diag, d_ref[0][:][None], 0.0)
    x_ref[:] = (_solve_tile_shared if shared else _solve_tile)(M, b_ref[:], k)


@functools.partial(jax.jit, static_argnames=("tile", "interpret",
                                             "vmem_limit", "shared"))
def _solve_padded(At, bt, tile: int, interpret: bool, d=None,
                  vmem_limit: Optional[int] = None, shared: bool = False):
    k = At.shape[0]
    n_pad = At.shape[2]
    lanes = pl.BlockSpec((k, tile), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_solve_kernel, k=k, shared=shared),
        grid=(n_pad // tile,),
        in_specs=[pl.BlockSpec((k, k, tile), lambda i: (0, 0, i)), lanes]
        + ([] if d is None else [pl.BlockSpec((1, tile), lambda i: (0, i))]),
        out_specs=lanes,
        out_shape=jax.ShapeDtypeStruct((k, n_pad), At.dtype),
        interpret=interpret,
        **_compiler_params(vmem_limit),
    )(At, bt, *(() if d is None else (d,)))


def _solve_kernel_batch_major(a_ref, b_ref, x_ref, *, k: int,
                              shared: bool = False):
    """Batch-major tile: A (T, k, k), b (T, k) -> x (T, k).  The lane-major
    transpose happens INSIDE the kernel (VMEM-resident vector shuffles),
    so XLA never lays out a lane-major operand for the whole array —
    inside a lax.map/scan body that layout materialized as a degenerate-
    dim copy lane-padded x128 (62.5 GB for a (43648, 50, 50) chunk, the
    round-3 fused-mode AOT OOM)."""
    # (k, k, T) in VMEM, a row of every system at a time: A[:, i, :] is
    # (T, k), and its 2-D transpose the slab M[i].  The one 3-D transpose
    # of the block cost more than the factorization it fed (254 ns a system
    # at k = 100 against 183 this way, TPU v5e, PR 46; section 7 there).
    M = jnp.stack([a_ref[:, i, :].T for i in range(k)], axis=0)
    b = jnp.transpose(b_ref[:], (1, 0))           # (k, T)
    x = (_solve_tile_shared if shared else _solve_tile)(M, b, k)
    x_ref[:] = jnp.transpose(x, (1, 0))


@functools.partial(jax.jit, static_argnames=("tile", "interpret",
                                             "vmem_limit", "shared"))
def _solve_padded_batch_major(Ab, bb, tile: int, interpret: bool,
                              vmem_limit: Optional[int] = None,
                              shared: bool = False):
    n_pad, k = bb.shape
    kernel = functools.partial(_solve_kernel_batch_major, k=k, shared=shared)
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


def cholesky_solve_batched(A, b, *, interpret: bool, layout="lane_major",
                           shared: bool = False):
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
    by never asking XLA for that layout.

    ``shared``: the caller makes many calls at this rank in one program (a
    side solved per chunk: one a bucket, in either layout), so the kernels
    call the elimination through ``_solve_tile_shared`` and trace it once
    between them."""
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
            Ab, bb, tile, bool(interpret), vmem_limit=vmem_limit,
            shared=shared)[:n]
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
    x = _solve_padded(At, bt, tile, bool(interpret), vmem_limit=vmem_limit,
                      shared=shared)
    return jnp.transpose(x[:, :n], (1, 0))
