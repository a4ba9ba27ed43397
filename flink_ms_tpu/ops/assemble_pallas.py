"""One-pass ALS normal-equation assembly as a Pallas TPU kernel.

A bucket's half-sweep gathers ``y (r, w, k)`` — for each of r entities the
k-wide opposite factor rows of its w (padded) ratings — only to contract
it: ``A = Σ_w y yᵀ`` and ``b = Σ_w t·y``.  XLA's program for the two
einsums reads that tensor three more times after the gather wrote it (a
``copy`` that brings w onto the lanes for the transposed matmul operand,
the A convolution, a multiply-reduce for b), every row padded from k to
128 lanes in HBM (chip trace, PERF.md §5: 54 + 44 + 17 ms of a 229 ms
ML-20M iteration).  This kernel reads it once, in the layout the gather
left it:

- grid ``(cdiv(r, C), cdiv(w, Wt))``: C entities per step, the rating
  axis tiled by Wt and accumulated into the resident ``(C, k, k)`` /
  ``(C, k)`` output blocks (the item side's widest lists are 97,096 rows
  of 512 B: one entity does not fit VMEM);
- in VMEM, a sublane tile of 8 entities at a time: the ``(8, Wt, k)`` slab
  is transposed on the XLU, the ratings ``t`` (whose rating axis already
  lies on the lanes) become row k of the transposed operand, and ONE
  batched MXU contraction ``(8, k+1, Wt) × (8, Wt, k)`` yields A in rows
  0..k-1 and b in row k.  Written as batched operations, not as eight
  unrolled bodies: Mosaic unrolls them either way (same device time on
  the chip), but every unrolled body is traced and lowered in Python on
  every start of the program, compile cache or not (8.7 s against 2.8 s
  for the 33 buckets of an ML-20M sweep, chip host);
- pad entries stay exact zeros through ``y`` itself; only the ragged last
  w tile is masked (an out-of-bounds block read is not zeros).

``precision`` is the caller's: "highest" contracts f32 products (six bf16
passes, as the einsum it replaces), "default" rounds both operands to
bf16 first, which is what one MXU pass means.  ``interpret`` comes from
the platform of the caller's mesh, as for ``cholesky_solve_batched``.
Which buckets take this path is ``ops/als.resolve_assembly``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .cholesky_pallas import _round_up

_GROUP = 8                 # entities per batched step: one f32 sublane tile
_MAX_WT = 1024             # rating rows per step once w is tiled (lane tiles)
_VMEM_BUDGET = 10 << 20    # of the 16 MB scoped VMEM, for the pipelined blocks


def tile_sizes(w: int, k: int):
    """-> (C, Wt): entities and rating rows per grid step.  A bucket up to
    ``_MAX_WT`` wide is contracted whole, and C fills the VMEM budget with
    double-buffered ``(C, Wt, k)`` input and ``(C, k, k)`` output blocks,
    both lane-padded to 128.  A wider bucket (few entities, long lists)
    takes one group of entities and equal tiles of whole lane tiles, at
    least half ``_MAX_WT`` each.  The last tile's overhang is computed on
    masked zeros (chip: 1120 as 1024 + 96 ran at 1.8x the read's time,
    evenly tiled widths at 1.1x), so the split that overhangs least wins:
    1120 -> 3 x 384.  The budget counts the pipelined blocks alone; a
    group's temporaries (the 128-lane slab of 8 x Wt rows, its transpose,
    the masked copy of a ragged tile) live in the rest of the scoped 16 MB:
    the widest case, w = 3000 as 1024-row tiles with a ragged last one,
    compiles and agrees with float64 on the chip at k = 50, 64, 100, 128."""
    if w > _MAX_WT:
        n = -(-w // _MAX_WT)
        wt = min((_round_up(-(-w // m), 128) for m in range(n, 2 * n + 1)),
                 key=lambda t: (t * -(-w // t), -t))
        return _GROUP, wt
    row = _round_up(k, 128) * 4
    per_entity = 2 * ((w + _round_up(k, 8)) * row + _round_up(w, 128) * 4)
    return max(_VMEM_BUDGET // per_entity // _GROUP * _GROUP, _GROUP), w


def _assemble_kernel(y_ref, t_ref, a_ref, b_ref, *, w: int, k: int,
                     one_pass: bool):
    """One grid step: y (C, Wt, k), t (C, Wt) -> A (C, k, k), b (C, k)."""
    C, wt, _ = y_ref.shape
    tiled = wt < w
    ragged = w % wt != 0
    j = pl.program_id(1)
    if tiled:
        @pl.when(j == 0)
        def _():
            a_ref[...] = jnp.zeros_like(a_ref)
            b_ref[...] = jnp.zeros_like(b_ref)
    if ragged:
        left = w - j * wt        # rating rows of this tile inside the array
        y_keep = jax.lax.broadcasted_iota(jnp.int32, (wt, k), 0) < left
        t_keep = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, wt), 1) < left
    precision = None if one_pass else jax.lax.Precision.HIGHEST

    def put(ref, at, value):
        # a tiled bucket accumulates over its w tiles; a whole one is written
        ref[at] = ref[at] + value if tiled else value

    def group(g, carry):
        # one batched transpose and one batched contraction for a sublane
        # tile of entities: traced once, unrolled by Mosaic
        rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        y8 = y_ref[rows]                                    # (8, Wt, k)
        t8 = t_ref[rows, :]                                 # (8, Wt)
        if ragged:
            y8 = jnp.where(y_keep, y8, 0.0)
            t8 = jnp.where(t_keep, t8, 0.0)
        lhs = jnp.concatenate(
            [jnp.swapaxes(y8, 1, 2), t8[:, None, :]], axis=1)   # (8, k+1, Wt)
        if one_pass:
            lhs, y8 = lhs.astype(jnp.bfloat16), y8.astype(jnp.bfloat16)
        res = jax.lax.dot_general(
            lhs, y8, (((2,), (1,)), ((0,), (0,))), precision=precision,
            preferred_element_type=jnp.float32)             # (8, k+1, k)
        put(a_ref, rows, res[:, :k])
        put(b_ref, rows, res[:, k])
        return carry

    jax.lax.fori_loop(0, C // _GROUP, group, 0)


def assemble_bucket(y, t, *, precision: str, interpret: bool):
    """A = einsum("rwk,rwl->rkl", y, y), b = einsum("rwk,rw->rk", y, t) from
    one read of y.  y (r, w, k) float32 with w a multiple of 8, t (r, w);
    ``precision`` "highest" or "default"."""
    if precision not in ("highest", "default"):
        raise ValueError(f"assembly kernel precision {precision!r}")
    r, w, k = y.shape
    c, wt = tile_sizes(w, k)
    kernel = functools.partial(_assemble_kernel, w=w, k=k,
                               one_pass=precision == "default")
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(r, c), pl.cdiv(w, wt)),
        in_specs=[
            pl.BlockSpec((c, wt, k), lambda i, j: (i, j, 0)),
            pl.BlockSpec((c, wt), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((c, k, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((c, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, k, k), jnp.float32),
            jax.ShapeDtypeStruct((r, k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(y, t.astype(jnp.float32))
