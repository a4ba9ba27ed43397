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

The weighted form (``alpha``: implicit feedback, Hu-Koren-Volinsky over
play counts t) is the same read and the same contraction.  After the
transpose the rating axis lies on the lanes, where ``t8 (8, Wt)`` already
lies, so the confidence weights are one multiply there: rows 0..k-1 become
``y8ᵀ · (α t)[:, None, :]`` and row k ``1 + α t``, and the product yields
``A = Σ α t · y yᵀ`` and ``b = Σ (1 + α t) · y`` (``YᵀY`` and ``λI`` are the
caller's).  A pad or masked entry has t = 0: weight 0, and the 1 it leaves
in row k meets a zero row of y.  ``alpha`` is a static of the kernel body:
without it the body is the explicit one, not a multiply by ones.  XLA's
einsum pair needs a weighted copy ``yw`` of the whole gathered tensor for
this (fused into the convolution's operand on the chip, but behind the same
relayout copy); here nothing of that size is written.  On the chip, at
msd-ials' rank 64 and batch-major (a ``(64, 64)`` A is 32 KB of HBM an
entity, written beside 512 B read a rating): 1.01-1.20x the time of its
bytes at 819 GB/s from w = 216 up, both halves, 1.23-1.33x at w = 64 to 144,
1.52x at w = 40 and 1.79x at w = 24, where six MXU passes of
``(65, w) x (w, 64)`` an entity bound it; 31.2 + 70.0 ms an iteration over
41.57M + 41.53M padded ratings against the einsum pair's 47.9 + 108.2 and
its 91.0 ms of relayout copies (PERF.md section 5, PR 42).

Two output layouts, one contraction.  ``assemble_bucket`` writes A
``(r, k, k)``, b ``(r, k)``: batch-major, each 50x50 tile padded to 56x128
in HBM (28.7 KB an entity where 10 KB are data), which the Pallas solver
can only read after XLA has concatenated, padded and transposed it (32 ms
of the 154.8 ms ML-20M iteration, PERF.md section 5, PR 26).
``assemble_bucket_lanes`` writes what that solver reads, At ``(k, k, n)``
and bt ``(k, n)`` with the batch on the lanes and n = r rounded up to 128:

- grid ``(n / 128, S, cdiv(w, Wt))``: a lane tile of 128 entities is fed in
  S sub-blocks of Cs entities (``lane_tile_sizes``: as many as the same
  VMEM budget holds, 128 at w <= 64, 8 from w = 744) into a resident
  ``(128, k+1, k)`` accumulator; the tile's last step transposes it in
  VMEM, ``(1, 2, 0)`` as the batch-major solver kernel does, and writes one
  lane-dense ``(k, k, 128)`` block: 1.88 GB a sweep instead of 4.74;
- a sub-block wholly past r is skipped and fetches nothing (its block
  index is held at the last real one); lanes past r are written as zeros,
  which the solver's diagonal operand turns into identity systems.

On the chip the transposition costs 2.9 us a lane tile: hidden behind the
next block's DMA from w = 496 up, on top of the contraction's 9 us in the
narrow buckets, which are bound by the MXU's many small products, not by
HBM (w = 24: 3.05 ms against 2.38 batch-major and 0.84 for its bytes; all
33 buckets 44.3 ms against 43.1; behind them the 9.2 ms relayout copy and
the 11.1 ms reg add + pad are gone and the 12 ms concatenation is a 5.3 ms
join along the lanes: PERF.md sections 5 and 6, PR 30).
``ops/als._bucket_normal_eqs`` picks the layout: lane-major whenever the
Pallas solver takes the result and the bucket runs straight-line,
batch-major inside ``lax.map`` chunks and on the fused route.

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

from .cholesky_pallas import LANES, _round_up  # LANES: entities per
#                            lane-major output block, the solver's lane tile

_GROUP = 8                 # entities per batched step: one f32 sublane tile
_MAX_WT = 1024             # rating rows per step once w is tiled (lane tiles)
_VMEM_BUDGET = 10 << 20    # of the 16 MB scoped VMEM, for the pipelined blocks
# the lane-major form holds, beside the same pipelined input blocks, the
# resident (128, k+1, k) accumulator, its transpose and two (k, k, 128)
# output blocks (13 MB at k = 64): more than the default scoped 16 MB
_LANES_VMEM_LIMIT = 40 << 20


def _lanes_vmem_limit(k: int) -> int:
    """The scoped VMEM the lane-major form asks for.  Up to rank 64 the
    constant the cells' programs were compiled with (the v5e compiler needs
    22 MiB of it at k = 64).  Above, the resident blocks grow with k squared
    and pass it: counted as the pipelined inputs' budget plus six blocks of
    k + 1 rows (padded to 8 sublanes) x 128 lanes x 128 entities, f32, that
    is 49 MiB at k = 100 and 61 at k = 128 where the compiler needs 32 and 50
    (the least limit it accepts for a described v5e, bisected to the MiB at
    w = 8, 64, 1,024 and 327,712; PR 44), of the chip's 128 MiB."""
    if k <= 64:
        return _LANES_VMEM_LIMIT
    return _VMEM_BUDGET + 6 * _round_up(k + 1, 8) * _round_up(k, 128) * LANES * 4


def _input_bytes(w: int, k: int) -> int:
    """VMEM of one entity's double-buffered y and t rows, lane-padded."""
    return 2 * (w * _round_up(k, 128) * 4 + _round_up(w, 128) * 4)


def _split_w(w: int) -> int:
    """Wt for a bucket wider than ``_MAX_WT``: equal tiles of whole lane
    tiles, at least half ``_MAX_WT`` each.  The last tile's overhang is
    computed on masked zeros (chip: 1120 as 1024 + 96 ran at 1.8x the read's
    time, evenly tiled widths at 1.1x), so the split that overhangs least
    wins: 1120 -> 3 x 384."""
    n = -(-w // _MAX_WT)
    return min((_round_up(-(-w // m), 128) for m in range(n, 2 * n + 1)),
               key=lambda t: (t * -(-w // t), -t))


def tile_sizes(w: int, k: int):
    """-> (C, Wt): entities and rating rows per grid step.  A bucket up to
    ``_MAX_WT`` wide is contracted whole, and C fills the VMEM budget with
    double-buffered ``(C, Wt, k)`` input and ``(C, k, k)`` output blocks,
    both lane-padded to 128.  A wider bucket (few entities, long lists)
    takes one group of entities and ``_split_w``'s tiles.  The budget counts
    the pipelined blocks alone; a group's temporaries (the 128-lane slab of
    8 x Wt rows, its transpose, the masked copy of a ragged tile) live in
    the rest of the scoped 16 MB: the widest case, w = 3000 as 1024-row
    tiles with a ragged last one, compiles and agrees with float64 on the
    chip at k = 50, 64, 100, 128 (and at k = 100 and 128 for a described
    v5e at every width of ``netflix-als-f100``'s ladder, 8 to 327,712:
    PR 44)."""
    if w > _MAX_WT:
        return _GROUP, _split_w(w)
    per_entity = _input_bytes(w, k) + 2 * _round_up(k, 8) * _round_up(k, 128) * 4
    return max(_VMEM_BUDGET // per_entity // _GROUP * _GROUP, _GROUP), w


def lane_tile_sizes(w: int, k: int):
    """-> (Cs, Wt) of the lane-major form, whose output block is always one
    lane tile of 128 entities: the input arrives in sub-blocks of Cs
    entities, the largest power of two whose double-buffered rows fit the
    same budget (128 at w <= 64, 8 from w = 744), and a bucket wider than
    ``_MAX_WT`` is tiled over w as in ``tile_sizes``."""
    if w > _MAX_WT:
        return _GROUP, _split_w(w)
    cs = LANES
    while cs > _GROUP and cs * _input_bytes(w, k) > _VMEM_BUDGET:
        cs //= 2
    return cs, w


def _contract_groups(y_ref, t_ref, j, put, *, w: int, k: int, one_pass: bool,
                     alpha):
    """One grid step's contraction of y (C, Wt, k), t (C, Wt), w tile j: for
    each sublane tile g of 8 entities, ``put(g, res)`` receives
    ``res (8, k+1, k)``, A in rows 0..k-1 and b in row k.  ``alpha`` None is
    the explicit form; a number weights the transposed rows by ``alpha * t``
    and makes row k ``1 + alpha * t`` (the module's header)."""
    C, wt, _ = y_ref.shape
    ragged = w % wt != 0
    if ragged:
        left = w - j * wt        # rating rows of this tile inside the array
        y_keep = jax.lax.broadcasted_iota(jnp.int32, (wt, k), 0) < left
        t_keep = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, wt), 1) < left
    precision = None if one_pass else jax.lax.Precision.HIGHEST

    def group(g, carry):
        # one batched transpose and one batched contraction for a sublane
        # tile of entities: traced once, unrolled by Mosaic
        rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        y8 = y_ref[rows]                                    # (8, Wt, k)
        t8 = t_ref[rows, :]                                 # (8, Wt)
        if ragged:
            y8 = jnp.where(y_keep, y8, 0.0)
            t8 = jnp.where(t_keep, t8, 0.0)
        yt = jnp.swapaxes(y8, 1, 2)                         # (8, k, Wt)
        if alpha is not None:
            # the rating axis lies on the lanes of both: one multiply, each
            # entity's confidence row broadcast over the k sublanes.  A pad
            # or masked entry has t 0: weight 0, and its 1 in row k meets a
            # zero row of y8
            c8 = alpha * t8
            yt, t8 = yt * c8[:, None, :], 1.0 + c8
        lhs = jnp.concatenate([yt, t8[:, None, :]], axis=1)   # (8, k+1, Wt)
        if one_pass:
            lhs, y8 = lhs.astype(jnp.bfloat16), y8.astype(jnp.bfloat16)
        put(g, jax.lax.dot_general(
            lhs, y8, (((2,), (1,)), ((0,), (0,))), precision=precision,
            preferred_element_type=jnp.float32))            # (8, k+1, k)
        return carry

    jax.lax.fori_loop(0, C // _GROUP, group, 0)


def _assemble_kernel(y_ref, t_ref, a_ref, b_ref, *, w: int, k: int,
                     one_pass: bool, alpha):
    """One grid step: y (C, Wt, k), t (C, Wt) -> A (C, k, k), b (C, k)."""
    tiled = y_ref.shape[1] < w
    j = pl.program_id(1)
    if tiled:
        @pl.when(j == 0)
        def _():
            a_ref[...] = jnp.zeros_like(a_ref)
            b_ref[...] = jnp.zeros_like(b_ref)

    def put(g, res):
        # a tiled bucket accumulates over its w tiles; a whole one is written
        rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        a_ref[rows] = a_ref[rows] + res[:, :k] if tiled else res[:, :k]
        b_ref[rows] = b_ref[rows] + res[:, k] if tiled else res[:, k]

    _contract_groups(y_ref, t_ref, j, put, w=w, k=k, one_pass=one_pass,
                     alpha=alpha)


def _assemble_kernel_lanes(y_ref, t_ref, a_ref, b_ref, acc_ref, *, r: int,
                           w: int, k: int, one_pass: bool, alpha):
    """Grid step (i, s, j): entity sub-block s of lane tile i, w tile j.
    y (Cs, Wt, k), t (Cs, Wt) land in rows s*Cs.. of the resident
    ``acc (128, k+1, k)``; the lane tile's last step transposes it in VMEM
    and writes At (k, k, 128), bt (k, 128), lanes past r as exact zeros."""
    cs, wt, _ = y_ref.shape
    tiled = wt < w
    i, s, j = (pl.program_id(d) for d in range(3))
    base = pl.multiple_of(s * cs, _GROUP)

    # a sub-block wholly past r (the index map held its block at the last
    # real one, so nothing was fetched for it) is skipped; its acc rows and
    # a partial sub-block's rows past r hold leftovers, masked below
    @pl.when(i * LANES + base < r)
    def _():
        if tiled:
            @pl.when(j == 0)
            def _():
                acc_ref[pl.ds(base, cs)] = jnp.zeros((cs, k + 1, k),
                                                     jnp.float32)

        def put(g, res):
            rows = pl.ds(pl.multiple_of(base + g * _GROUP, _GROUP), _GROUP)
            acc_ref[rows] = acc_ref[rows] + res if tiled else res

        _contract_groups(y_ref, t_ref, j, put, w=w, k=k, one_pass=one_pass,
                         alpha=alpha)

    @pl.when((s == pl.num_programs(1) - 1) & (j == pl.num_programs(2) - 1))
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, LANES), 2)
        out = jnp.where(lane < r - i * LANES,
                        jnp.transpose(acc_ref[:], (1, 2, 0)), 0.0)
        a_ref[:] = out[:k]
        b_ref[:] = out[k]


def _one_pass(precision: str) -> bool:
    if precision not in ("highest", "default"):
        raise ValueError(f"assembly kernel precision {precision!r}")
    return precision == "default"


def assemble_bucket(y, t, *, precision: str, interpret: bool, alpha=None):
    """A = einsum("rwk,rwl->rkl", y, y), b = einsum("rwk,rw->rk", y, t) from
    one read of y.  y (r, w, k) float32 with w a multiple of 8, t (r, w);
    ``precision`` "highest" or "default".  With ``alpha`` (a Python number,
    implicit feedback over play counts t): A = einsum("rw,rwk,rwl->rkl",
    alpha * t, y, y), b = einsum("rwk,rw->rk", y, 1 + alpha * t)."""
    r, w, k = y.shape
    c, wt = tile_sizes(w, k)
    kernel = functools.partial(_assemble_kernel, w=w, k=k,
                               one_pass=_one_pass(precision), alpha=alpha)
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


def assemble_bucket_lanes(y, t, *, precision: str, interpret: bool,
                          alpha=None):
    """The same sums (``assemble_bucket``'s, ``alpha`` included) with the
    batch on the lanes, as the Pallas solver reads them: At (k, k, n),
    bt (k, n) with n = r rounded up to 128 and the lanes past r exact
    zeros."""
    r, w, k = y.shape
    cs, wt = lane_tile_sizes(w, k)
    n_sub = min(LANES // cs, pl.cdiv(r, cs))
    n_w = pl.cdiv(w, wt)
    last = pl.cdiv(r, cs) - 1     # the last entity block inside the array

    def y_block(i, s, j):
        e = i * n_sub + s
        return jnp.minimum(e, last), jnp.where(e > last, n_w - 1, j)

    n = _round_up(r, LANES)
    kernel = functools.partial(_assemble_kernel_lanes, r=r, w=w, k=k,
                               one_pass=_one_pass(precision), alpha=alpha)
    return pl.pallas_call(
        kernel,
        grid=(n // LANES, n_sub, n_w),
        in_specs=[
            pl.BlockSpec((cs, wt, k), lambda i, s, j: (*y_block(i, s, j), 0)),
            pl.BlockSpec((cs, wt), y_block),
        ],
        out_specs=[
            pl.BlockSpec((k, k, LANES), lambda i, s, j: (0, 0, i)),
            pl.BlockSpec((k, LANES), lambda i, s, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, k, n), jnp.float32),
            jax.ShapeDtypeStruct((k, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((LANES, k + 1, k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_lanes_vmem_limit(k)),
        interpret=interpret,
    )(y, t.astype(jnp.float32))


def to_lanes(A, b):
    """A (r, k, k), b (r, k) -> ``assemble_bucket_lanes``' layout by an XLA
    transpose and zero pad: for a bucket whose kernel had to stay
    batch-major (inside ``lax.map`` chunks)."""
    pad = -A.shape[0] % LANES
    return (jnp.pad(jnp.transpose(A, (1, 2, 0)), ((0, 0), (0, 0), (0, pad))),
            jnp.pad(jnp.transpose(b, (1, 0)), ((0, 0), (0, pad))))
