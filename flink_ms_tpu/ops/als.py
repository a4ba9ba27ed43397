"""Blocked alternating least squares on a TPU device mesh.

TPU-native re-design of the capability behind ``ALS().fit(inputDS, parameters)``
(reference call site ``flink-als/.../ALSImpl.scala:35-52``; solver semantics are
FlinkML's block-partitioned ALS [dep], SURVEY.md §2.2): user/item factor blocks
live sharded in HBM over a 1-D mesh, each half-sweep solves the per-ID
regularized normal equations

    (Y_Ωuᵀ Y_Ωu + λ·reg_u·I) x_u = Y_Ωuᵀ r_u

as a *batched Cholesky* (MXU-friendly), and the reference's per-iteration
factor-block shuffle over Netty becomes a single ``all_gather`` over ICI.

Ratings are laid out **degree-bucketed**: within each block, entities are
grouped by degree class (a geometric width ladder, default ratio 1.5 with
rungs rounded to multiples of 8 — FLINK_MS_ALS_BUCKET_RATIO) and each
group's rating lists are padded to the class width, so normal-equation
assembly is a short list of dense batched contractions — a row gather,
then MXU matmuls, no scatter.  On a TPU, with an f32 exchange and a rank up
to 128, in either mode, each bucket's gathered rows are contracted where
the gather left them by one Pallas kernel (``assemble_pallas.py``: A
and b from a single read, the transpose and implicit mode's confidence
weights done in VMEM, written batch-minor
as the Pallas solver reads them, which adds λ·reg to the diagonal itself:
between the two kernels A is joined along the lanes and, in implicit
mode, YᵀY added, and nothing else);
every other path (bf16 exchange, three-pass products, CPU) is the ``einsum``
pair, which XLA runs as a relayout copy, a convolution and a
multiply-reduce — ``resolve_assembly`` decides per sweep.  (A
scatter/``segment_sum`` formulation was measured 8-10x slower on v5e: TPU
scatter serializes per row, and XLA's batched small-matrix Cholesky
streams the whole (n, k, k) tensor per elimination step.)

Two training modes, each timed by a cell of the benchmark:

- explicit feedback (FlinkML parity; ``als-ml20m.retrain`` at rank 50,
  ``netflix-als-f100.retrain`` at rank 100): weighted-λ
  regularization (reg_u = n_u, Zhou et al. ALS-WR) or plain λ;
- implicit feedback (confidence-weighted, Hu-Koren-Volinsky;
  ``msd-ials.ials-retrain``):
  A_u = YᵀY + Σ_{i∈Ωu} α·r_ui · y_i y_iᵀ + λ·I with YᵀY a ``psum`` of
  per-shard Gramians (scope ``als.gram``), plain λ; the weights α·r and
  1 + α·r are applied inside the assembly kernel, or by XLA (scope
  ``als.weight``) before the einsum pair.

A half-sweep whose gather table is too large for the chip's fast memory
reads it through S row segments that fit (``table_segments`` decides from
the table's bytes; ``cut_side`` cuts the lists at the segments' boundaries):
a row gathered from fast memory costs a third of one from HBM.

Each half-sweep either materialises its (per_block, k, k) normal equations
and solves them in one batch, or solves every assembly chunk where it was
assembled so that the tensor never exists: ``solves_per_chunk`` decides per
side from the tensor's bytes and the device's memory.

Everything under ``jit`` is static-shaped; the iteration loop is a
``fori_loop`` so a full fit is one XLA program.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import re
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..obs import metrics as obs_metrics
from ..obs import tracing
from ..parallel.mesh import (
    BLOCK_AXIS,
    block_sharding,
    device_memory,
    fast_memory,
    host_device,
    num_blocks,
)

# ---------------------------------------------------------------------------
# config + host-side problem layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ALSConfig:
    """Mirrors the reference's surfaced parameters (ALSImpl.scala:35-49) plus
    the implicit-feedback mode (``implicit``, ``alpha``: Hu-Koren-Volinsky's
    confidence c = 1 + alpha*r over play or click counts, plain lambda
    whatever ``weighted_reg`` says), the mode of ``als_train --implicit
    true`` and of the benchmark's ``msd-ials`` configuration."""

    num_factors: int = 10
    iterations: int = 10
    lambda_: float = 0.9
    seed: int = 42
    implicit: bool = False
    alpha: float = 40.0          # implicit confidence scale, c = 1 + alpha*r
    weighted_reg: bool = True    # ALS-WR: lambda * n_u (FlinkML semantics)
    dtype: jnp.dtype = jnp.float32
    # MXU pass count for the assembly einsums: "highest" = full-f32 products
    # (6-pass bf16), "high" = 3-pass, "default" = single-pass bf16 (fastest,
    # shifts the normal equations ~1e-3 relative) — benchmark knob
    assembly_precision: str = "highest"
    # Factor-EXCHANGE dtype: "bfloat16" halves both the all_gather bytes
    # over ICI and the random-row gather's HBM traffic (a different lever
    # than assembly_precision — that one changes MXU passes, this one
    # changes the bytes moved).  Normal equations still accumulate in the
    # solve dtype via preferred_element_type.  None = full precision;
    # "auto" (the default) resolves per backend in resolve_exchange():
    # bfloat16 on TPU and full precision elsewhere.  At the ML-20M shape
    # on one v5e the two answers are two cells of the benchmark, same
    # shape and seeds: `als-ml20m-bf16x.retrain-bf16x` (this default: bf16
    # rows, the einsum pair) 0.1771 s/iter beside `als-ml20m.retrain`
    # (None: f32 rows, the kernel) 0.1299: the 2-byte take is no faster a
    # row (1.48 ns against 1.42) and XLA relays rows and A out around its
    # convolutions (chip runs, PR 49; PERF.md section 5; ROADMAP S5 (e)).
    exchange_dtype: Optional[str] = "auto"


_MIN_BUCKET_W = 8  # smallest rating-list pad width (sublane-friendly)

# Zero slots at the tail of every block of a factor table (the strip).  The
# OPPOSITE side's pad entries gather them, spread so that no strip slot is
# named twice within _PAD_STRIP consecutive positions of a bucket's flat
# (rows, w) order.  A table that lives in HBM serves one row named over and
# over more slowly than distinct rows: with every pad of a side on ONE slot
# msd-ials' item half gathered its 41.6M rows (19% of them pads) at 4.9-11.8
# ns a row by bucket, the longer the pad runs the slower; spread, at 3.95 in
# every bucket but the widest, which is what its 3%-pad bucket always cost
# (0.7200 -> 0.6596 s/iter; a table in fast memory, als-ml20m's two, never
# cared).  Chosen once on the chip (TPU v5e, PR 34, PERF.md section 6): the
# gather alone reads 8.55 ns a row at 1 slot, 7.14 at 8 and 7.10 at 128,
# 1,024 and 8,192, the same as pads on distinct real rows; 128 is the
# smallest size probed that is on the plateau in every bucket, and one lane
# tile.  A constant, not a knob: it costs a block 127 rows of zeros.
_PAD_STRIP = 128


@dataclasses.dataclass
class SideLayout:
    """Degree-bucketed layout of one orientation (user- or item-major).

    Entities of a block are grouped by degree class; class j pads every
    member's rating list to ``widths[j]`` columns.  The factor table itself
    lives in *slot order* on device — ``perm`` maps dense entity index to
    its global slot ``block * per_block + local`` — so bucket outputs are
    contiguous rows and the solve writes factors with no scatter.

    The buckets' ``idx`` arrays are contiguous slices of one buffer a side,
    as are the ``val`` (``_fill_side`` writes every rating once, into it):
    whoever holds one bucket's array holds the side's buffer.
    """

    per_block: int            # slots per block (Σ_j rows[j] + _PAD_STRIP:
    #                           every block ends in a strip of guaranteed-
    #                           zero slots)
    n_rows: int               # real entity count
    perm: np.ndarray          # (n_rows,) dense index -> global slot
    widths: Tuple[int, ...]   # pad width per bucket, descending
    rows: Tuple[int, ...]     # rows per bucket per block (static across blocks)
    idx: list                 # per bucket: (D, rows[j], widths[j]) int32,
    #                           opposite-side global slot of each rating;
    #                           PAD entries are spread over the strip of
    #                           the opposite side's block of the same
    #                           number, so gathered pad rows are exact
    #                           zeros and assembly needs no mask arrays at
    #                           all
    val: list                 # per bucket: ratings, pad entries 0
    count: np.ndarray         # (D, per_block) degree per slot (0 on the strip)


@dataclasses.dataclass
class BlockedProblem:
    """Ratings re-laid-out for a D-block mesh (host-side, numpy).

    The analog of FlinkML's user-block x item-block routing tables [dep]:
    instead of routing messages, each block holds the degree-bucketed pad
    layout of the ratings it owns in both orientations, and factor exchange
    is an all_gather — or, when the need-lists are sparse enough, a routed
    all_to_all over them (``_exchange_plan``).
    """

    n_blocks: int
    user_ids: np.ndarray      # (n_users,) raw ids, sorted
    item_ids: np.ndarray      # (n_items,) raw ids, sorted
    nnz: int
    u: SideLayout             # user-major (solves user factors)
    i: SideLayout             # item-major (solves item factors)
    # lazily built routed-exchange plans, keyed by (D, mode choice) —
    # see _exchange_plan
    routing: dict = dataclasses.field(default_factory=dict, repr=False)
    # lazily cut sides, keyed by (side, segments) — see _cuts
    cuts: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def n_users(self) -> int:
        return int(self.user_ids.shape[0])

    @property
    def n_items(self) -> int:
        return int(self.item_ids.shape[0])

    # factor-table slot counts (include bucket-padding rows and the strip)
    @property
    def users_per_block(self) -> int:
        return self.u.per_block

    @property
    def items_per_block(self) -> int:
        return self.i.per_block


def _dense_ids(arr: np.ndarray):
    """``np.unique(arr, return_inverse=True)`` with an O(n) fast path.

    Rating files carry small non-negative integer ids (ML-20M: user ids
    ≤ 138k), where a presence bitmap + cumsum replaces unique's O(n log n)
    sort over all nnz entries.  Sparse/huge/negative/non-integer ids fall
    back to unique; both paths return sorted unique ids + dense inverse.
    """
    if np.issubdtype(arr.dtype, np.integer) and arr.size:
        mx = int(arr.max())
        if int(arr.min()) >= 0 and mx <= max(4 * arr.size, 1 << 20):
            present = np.zeros(mx + 1, dtype=bool)
            present[arr] = True
            ids = np.nonzero(present)[0]
            lookup = np.cumsum(present) - 1
            return ids, lookup[arr]
    return np.unique(arr, return_inverse=True)


def _bucket_ratio() -> float:
    """FLINK_MS_ALS_BUCKET_RATIO, validated.  In multi-process runs the
    value must be identical on every host (the ladder determines the
    sharded factor-table shapes the collectives agree on) — pass an
    explicit ``bucket_ratio`` to ``prepare_blocked`` to pin it."""
    import math

    raw = os.environ.get("FLINK_MS_ALS_BUCKET_RATIO", "1.5")
    try:
        ratio = float(raw)
    except ValueError:
        raise ValueError(
            f"FLINK_MS_ALS_BUCKET_RATIO={raw!r} is not a number"
        ) from None
    if not math.isfinite(ratio) or not (1.05 <= ratio <= 16.0):
        raise ValueError(
            f"FLINK_MS_ALS_BUCKET_RATIO={raw!r} must be a finite value in "
            "[1.05, 16]"
        )
    return ratio


def _side_order(row_idx: np.ndarray, n_rows: int, n_blocks: int,
                ratio: Optional[float] = None):
    """Degree-sorted block layout of one side -> (deg, block_of, bucket_of,
    perm, widths, rows, per_block).

    Entities are split into D contiguous dense-index blocks (the reference's
    ``setBlocks`` partitioning), then within each block ordered by degree
    descending so each degree bucket is a contiguous slot range.
    """
    dense_pb = -(-n_rows // n_blocks)  # dense entities per block (ceil)
    deg = np.bincount(row_idx, minlength=n_rows).astype(np.int64)
    block_of = np.arange(n_rows) // dense_pb
    # within-block order: degree desc, dense index as tiebreak
    order = np.lexsort((np.arange(n_rows), -deg, block_of))
    # bucket widths: geometric ladder from _MIN_BUCKET_W up to max degree,
    # each rung rounded up to a multiple of 8 (f32 sublane).  Ratio 1.5
    # (default, FLINK_MS_ALS_BUCKET_RATIO) measured 14-21% faster full
    # sweeps than the classic power-of-two ladder (2.0) on both uniform
    # ML-20M-shaped and zipf-skewed data: a degree distribution sitting
    # just above a pow-2 rung pads up to ~1.8x, while finer rungs cost
    # only a few extra einsum dispatches inside the same jit.  1.25 wins
    # a little more on uniform data but over-fragments skewed catalogs.
    if ratio is None:
        ratio = _bucket_ratio()
    max_deg = max(int(np.max(deg)), 1)
    ladder = [_MIN_BUCKET_W]
    while ladder[-1] < max_deg:
        nxt = int(-(-int(ladder[-1] * ratio) // 8) * 8)  # round up to 8
        if nxt <= ladder[-1]:
            nxt = ladder[-1] + 8
        ladder.append(nxt)
    widths_all = np.array(ladder[::-1])  # descending
    # bucket of an entity = smallest rung >= its degree (ladder ascending
    # -> searchsorted left on the ascending view, then flip the index)
    asc = widths_all[::-1]
    pos = np.searchsorted(asc, np.maximum(deg, 1), side="left")
    bucket_of = len(widths_all) - 1 - pos
    # per (block, bucket) entity counts -> static rows per bucket = max over blocks
    counts_bb = np.zeros((n_blocks, len(widths_all)), dtype=np.int64)
    np.add.at(counts_bb, (block_of, bucket_of), 1)
    rows_per_bucket = counts_bb.max(axis=0)
    keep = rows_per_bucket > 0
    widths = tuple(int(x) for x in widths_all[keep])
    rows = tuple(int(x) for x in rows_per_bucket[keep])
    # remap bucket ids to the kept, descending-width list
    remap = np.cumsum(keep) - 1
    bucket_of = remap[bucket_of]
    offsets = np.concatenate([[0], np.cumsum(rows)])  # slot offset per bucket
    # every block ends in the strip: _PAD_STRIP slots whose factor rows are
    # zero for the life of the fit (zero-filled at init in _pad_factors,
    # kept zero by the count==0 mask in _solve_factors), and the OPPOSITE
    # side's pad gathers are spread over them (_fill_side)
    per_block = int(offsets[-1]) + _PAD_STRIP
    # rank of each entity within its (block, bucket), following `order`
    sorted_b = block_of[order]
    sorted_j = bucket_of[order]
    key = sorted_b * len(widths) + sorted_j
    starts = np.searchsorted(key, np.arange(n_blocks * len(widths) + 1))
    rank = np.arange(n_rows) - starts[key]
    perm_sorted = sorted_b * per_block + offsets[sorted_j] + rank
    perm = np.empty(n_rows, dtype=np.int64)
    perm[order] = perm_sorted
    return deg, block_of, bucket_of, perm, widths, rows, per_block


def _strip_slots(n: int) -> np.ndarray:
    """Offsets into a strip for ``n`` consecutive pad positions: position p
    takes slot p mod _PAD_STRIP, so a slot comes back only after every
    other has been named."""
    return np.arange(n, dtype=np.int32) % _PAD_STRIP


# Entries a step of the fill's two passes (key build; decode and scatter): a
# step's temporaries, 2 MB each, are the allocator's to hand out again.  From
# 32 MB each (2^22) they are mapped and faulted in anew every pass, as a whole
# array is, and the fill takes 2.3x as long (PERF.md section 6, PR 52)
_FILL_STEP = 1 << 18


def _sorted_keys(row_idx, col_idx, opp_perm, n_rows, n_slots):
    """One side's ratings by (row, opposite slot) -> ``(key, order, cb, ib)``.

    Where row, slot and entry fit 64 bits together (read from the sizes:
    ``n_rows``, the ``n_slots`` of the opposite table, the entries) the key
    is ``row << (cb + ib) | slot << ib | entry`` and comes back sorted, as a
    value, in place: numpy's 64-bit ``sort`` is vectorised where its
    ``argsort`` is not, or less, and what a sorted entry needs (its row, its
    slot, its place in the input) are bit fields of its key, so nothing is
    gathered through an order; ``order`` is None.  Keys are distinct, and
    two ratings of one (row, slot) pair lie in input order.  Else (1M x 1M
    with 2^27 ratings would be 67 bits) the key is the fused
    ``row << 32 | slot`` in INPUT order, ``ib`` is 0 and ``order`` its
    ``argsort``, which leaves such a pair in either order; both fields fit
    by construction, a slot being an int32 in ``idx``.  No cell of the
    benchmark takes that branch, and what it costs at a real size is unread
    (PERF.md section 7)."""
    nnz = row_idx.shape[0]
    cb = max(n_slots - 1, 1).bit_length()
    ib = max(nnz - 1, 1).bit_length()
    fits = max(n_rows - 1, 1).bit_length() + cb + ib <= 64
    if not fits:
        cb, ib = 32, 0
    key = np.empty(nnz, np.uint64)
    for s in range(0, nnz, _FILL_STEP):
        k = row_idx[s:s + _FILL_STEP].astype(np.uint64)
        k <<= np.uint64(cb)
        k |= opp_perm[col_idx[s:s + _FILL_STEP]].astype(np.uint64)
        if fits:
            k <<= np.uint64(ib)
            k |= np.arange(s, s + k.shape[0], dtype=np.uint64)
        key[s:s + _FILL_STEP] = k
    if fits:
        key.sort()
        return key, None, cb, ib
    return key, np.argsort(key), cb, ib


def _fill_side(
    keys, vals, n_rows, n_blocks, side_order, opp_per_block, dtype
) -> SideLayout:
    """Build one side's bucketed arrays from its precomputed ``_side_order``
    result and its ``_sorted_keys``, whose slots are the opposite side's
    global slots (the positions valid against the all_gather'd factor
    table); ``opp_per_block`` is the opposite side's slots per block, whose
    last _PAD_STRIP are the strip: factor rows guaranteed zero — pad
    entries gather them, so no mask array exists.  Block d's pads name the
    strip of the opposite side's block d (its own shard: pads never ride a
    routed exchange), the position p of a bucket's flat (rows, w) order the
    strip's slot p mod _PAD_STRIP: within a list addresses ascend up to
    where the strip wraps, and no slot is named twice within _PAD_STRIP
    consecutive positions.

    The ratings were sorted once and each is written once, into one buffer
    a side of which the buckets' arrays are slices.  Within a list entries
    ascend by opposite slot, and two ratings of one (row, slot) pair keep
    the input's order wherever the key holds the entry (every shape the
    benchmark has): the layout is then a function of the input's order
    alone, which the unstable ``argsort`` of before never promised (such a
    pair's two ``val`` may lie the other way round than they did)."""
    key, order, cb, ib = keys
    deg, block_of, bucket_of, perm, widths, rows, per_block = side_order
    rows_of, widths_of = np.asarray(rows), np.asarray(widths)
    # bucket j is flat[starts[j]:starts[j + 1]], (n_blocks, rows[j], widths[j])
    starts = np.concatenate([[0], np.cumsum(n_blocks * rows_of * widths_of)])
    flat_idx = np.empty(starts[-1], np.int32)
    flat_val = np.zeros(starts[-1], dtype)
    strip = (np.arange(n_blocks, dtype=np.int32)[:, None, None] * opp_per_block
             + (opp_per_block - _PAD_STRIP))
    idx, val = [], []
    for j in range(len(widths)):
        shape = (n_blocks, rows[j], widths[j])
        idx.append(flat_idx[starts[j]:starts[j + 1]].reshape(shape))
        val.append(flat_val[starts[j]:starts[j + 1]].reshape(shape))
        np.add(strip, _strip_slots(rows[j] * widths[j]).reshape(shape[1:]),
               out=idx[j])
    count = np.zeros((n_blocks, per_block), dtype)
    local = perm - block_of * per_block  # slot within block
    offsets = np.concatenate([[0], np.cumsum(rows)])
    count[(block_of, local)] = deg.astype(dtype)

    # the ratings lie sorted by owning entity -> contiguous per-entity
    # runs, in dense-index order; the secondary sort by opposite slot makes
    # each rating list's factor gather walk HBM in ascending address order
    # (contractions are order-invariant, so this only changes DMA locality).
    # Ragged fill, all buckets at once: an entity's list starts at `first`
    # in the flat buffer and at `cumsum(deg)` in the sorted ratings, so the
    # sorted entry at place p goes to `ahead[its row] + p`
    first = starts[bucket_of] + widths_of[bucket_of] * (
        block_of * rows_of[bucket_of] + local - offsets[bucket_of])
    ahead = first - (np.cumsum(deg) - deg)
    entry_mask, slot_mask = np.uint64((1 << ib) - 1), np.uint64((1 << cb) - 1)
    for s in range(0, key.shape[0], _FILL_STEP):
        if order is None:
            k = key[s:s + _FILL_STEP]
            entry = (k & entry_mask).view(np.int64)
            k = k >> np.uint64(ib)
        else:
            entry = order[s:s + _FILL_STEP]
            k = key[entry]
        dst = ahead[(k >> np.uint64(cb)).view(np.int64)]
        dst += np.arange(s, s + k.shape[0])
        flat_idx[dst] = k & slot_mask
        flat_val[dst] = vals[entry]
    return SideLayout(
        per_block=per_block,
        n_rows=n_rows,
        perm=perm,
        widths=widths,
        rows=rows,
        idx=idx,
        val=val,
        count=count,
    )


def prepare_blocked(
    users: np.ndarray,
    items: np.ndarray,
    ratings: np.ndarray,
    n_blocks: int,
    dtype=np.float32,
    bucket_ratio: Optional[float] = None,
) -> BlockedProblem:
    """Build the blocked layout: dense-reindex raw ids, split entities into
    D contiguous blocks, degree-sort within blocks, and emit the bucketed
    pad layout per block in both orientations.  ``bucket_ratio`` pins the
    width-ladder growth factor (default: validated
    FLINK_MS_ALS_BUCKET_RATIO env, 1.5) — multi-process launchers should
    pass it explicitly so every host builds identical shapes.  Phases: the
    root ``als.prepare`` with ``als.prepare.order`` (dense ids, degrees,
    ladder, perms) and ``als.prepare.fill`` (both sides' key sort, its
    child ``als.prepare.fill.sort``, then both sides' ragged fill)."""
    with tracing.phase("als.prepare"):
        with tracing.phase("als.prepare.order"):
            users = np.asarray(users)
            items = np.asarray(items)
            ratings = np.asarray(ratings, dtype=np.float64)
            if users.shape[0] == 0:
                raise ValueError("empty ratings input")

            user_ids, u_idx = _dense_ids(users)
            item_ids, i_idx = _dense_ids(items)

            # slot orders first: each side's idx arrays point at the
            # OPPOSITE side's slots, so both perms must exist before either
            # fill
            ratio = bucket_ratio if bucket_ratio is not None \
                else _bucket_ratio()
            u_order = _side_order(u_idx, len(user_ids), n_blocks, ratio)
            i_order = _side_order(i_idx, len(item_ids), n_blocks, ratio)
            u_perm, i_perm = u_order[3], i_order[3]
        # each side's pad gathers are spread over the opposite side's strip
        # (the tail of every block), found from its slots per block
        # side by side, in two stages: each side's sort of all the ratings,
        # then its ragged fill; numpy lets go of the interpreter's lock
        # inside both
        with tracing.phase("als.prepare.fill"), ThreadPoolExecutor(2) as pool:
            with tracing.phase("als.prepare.fill.sort"):
                keys = list(pool.map(
                    _sorted_keys, (u_idx, i_idx), (i_idx, u_idx),
                    (i_perm, u_perm), (len(user_ids), len(item_ids)),
                    (n_blocks * i_order[6], n_blocks * u_order[6])))
            u_side, i_side = pool.map(
                _fill_side, keys, (ratings, ratings),
                (len(user_ids), len(item_ids)), (n_blocks, n_blocks),
                (u_order, i_order), (i_order[6], u_order[6]), (dtype, dtype))
    return BlockedProblem(
        n_blocks=n_blocks,
        user_ids=user_ids,
        item_ids=item_ids,
        nnz=int(len(ratings)),
        u=u_side,
        i=i_side,
    )


# ---------------------------------------------------------------------------
# a gather table too large for fast memory, read through segments that fit
# ---------------------------------------------------------------------------

# What a gathered row costs is set by where its table lies (TPU v5e, PERF.md
# sections 5 and 6): 1.33-1.41 ns from a table XLA keeps in the chip's fast
# memory (``S(1)`` on the table's layout in the compiled program; both of
# als-ml20m's tables, 71 MB the larger), 3.95 or 9.9 ns from one in HBM,
# which of the two by the take's shape and erratically.  A half-sweep whose
# table cannot lie there gathers from S row segments that can, its lists cut
# at the segments' boundaries (``cut_side``).
#
# Beside a table the fast memory holds the assembly kernel's scoped limit
# (``assemble_pallas._lanes_vmem_limit``) and this much of XLA's own (its
# fusions' default scoped limit is 16 MiB).  Bracketed by compiles for a
# described v5e (``scripts/als_compiled_layout.py``; nothing runs there, and
# the chip then read what they said, PERF.md section 6, PR 45): beside the
# 40 MB kernel als-ml20m's 71.0 MB table and msd-ials' four 73.2 MB
# segments are all placed in S(1) and three segments of 97.6 MB are not;
# beside the 49 MiB kernel netflix-als-f100's four of 61.5 MB are.  The
# budget this leaves is 75.5 MB at rank 64 and 66.0 at 100.
_FAST_MEMORY_SLACK = 16 << 20
_CUT_THREADS = 8   # ``cut_side``'s, one bucket each


def table_segments(rows: int, k: int, itemsize: int,
                   fast_bytes: Optional[int], reserved: int) -> int:
    """S: into how many row segments a half-sweep cuts the ``rows``-slot
    factor table it gathers from (the strip among them).  1 wherever the
    table, at the whole lane tiles its rows occupy, fits ``fast_bytes`` (a
    device's fast memory, ``mesh.fast_memory``) less ``reserved`` (the
    assembly kernel's scoped limit) less ``_FAST_MEMORY_SLACK``, and wherever
    no fast memory is reported (the CPU); else the fewest equal segments of
    its real rows that fit, each beside a strip of its own."""
    if not fast_bytes:
        return 1
    row_bytes = -(-k // _LANES) * _LANES * itemsize
    budget = fast_bytes - reserved - _FAST_MEMORY_SLACK
    if rows * row_bytes <= budget:
        return 1
    real = rows - _PAD_STRIP
    fit = max(int(budget // row_bytes) - _PAD_STRIP, 1)
    return -(-real // fit)


@dataclasses.dataclass
class CutSide:
    """One side's rating lists cut at the boundaries of ``segments`` equal
    row ranges of the table its half-sweep gathers from (one device only).

    Bucket j's ``(rows_j, w_j)`` lists are stored as S pieces ``(rows_j,
    w_js)``: piece s holds, for every list, the run of its entries whose
    opposite slot lies in segment s (contiguous, because ``_fill_side`` sorts
    a list by slot), rebased to the segment and padded to ``w_js``
    (``_piece_width``).  Segment s is read as ``seg_rows`` table rows
    followed by a zero strip of its own, and a piece's pads are spread over
    that strip by ``_PAD_STRIP``'s rule."""

    segments: int     # S
    seg_rows: int     # table rows a segment holds (the last may hold fewer)
    widths: list      # per bucket: the S piece widths
    idx: list         # per bucket: S arrays (1, rows_j, w_js) int32
    val: list         # per bucket: S arrays of ratings, pad entries 0


def _piece_width(w: int, share: float, longest: int) -> int:
    """Width of a piece of a ``w``-wide bucket in a segment that holds
    ``share`` of the opposite side's ratings: what a list of w entries paired
    at random runs to there, w * share, plus six standard deviations of that
    count, or the ``longest`` run found where the data are not paired so;
    a multiple of 8, at most w.  From the degrees alone wherever the first
    governs, as the buckets' own shapes are: the pieces of two days' ratings
    with one degree law (the benchmark's seeds) have one shape and one
    compiled program, where the longest run moves with every pairing (by
    some 200 entries in 167,000 at netflix-als-f100: 0.6% of an iteration
    and a compile of 90 s a seed; chip run, PERF.md section 6, PR 45)."""
    spread = 6.0 * (w * share * (1.0 - share)) ** 0.5
    fit = max(int(np.ceil(w * share + spread)), longest, 1)
    return min(-(-fit // 8) * 8, w)


def cut_side(side: SideLayout, opp: SideLayout, segments: int) -> CutSide:
    """``side`` (one block) as a ``CutSide`` over the table of ``opp``'s
    slots.  No rating is dropped or moved to another list: a list's entries
    keep their order, and a list with no entry in a segment has pads alone
    there."""
    real = opp.per_block - _PAD_STRIP        # slots below the table's strip
    seg_rows = -(-real // segments)
    held = np.add.reduceat(opp.count[0, :real].astype(np.float64),
                           np.arange(0, real, seg_rows))
    shares = held / held.sum()               # of the ratings, by segment

    def cut_bucket(ix, vl):
        rows = ix.shape[1]
        keep = ix[0] < real                              # not a pad
        slots, values = ix[0][keep], vl[0][keep]         # list-major, ascending
        seg = slots // seg_rows
        row_of = np.repeat(np.arange(rows), np.count_nonzero(keep, axis=1))
        runs = np.bincount(row_of * segments + seg,
                           minlength=rows * segments).reshape(rows, segments)
        widths, idx, val = [], [], []
        for s in range(segments):
            w = _piece_width(ix.shape[2], shares[s], int(runs[:, s].max()))
            piece = seg_rows + _strip_slots(rows * w).reshape(1, rows, w)
            rating = np.zeros((1, rows, w), vl.dtype)
            into = np.arange(w) < runs[:, s, None]       # a run fills from 0
            mine = seg == s
            piece[0][into] = slots[mine] - s * seg_rows
            rating[0][into] = values[mine]
            widths.append(w)
            idx.append(piece)
            val.append(rating)
        return tuple(widths), idx, val

    # a bucket a task: numpy's passes release the interpreter's lock
    with ThreadPoolExecutor(_CUT_THREADS) as pool:
        widths, idx, val = zip(*pool.map(cut_bucket, side.idx, side.val))
    return CutSide(segments=segments, seg_rows=seg_rows, widths=list(widths),
                   idx=list(idx), val=list(val))


# ---------------------------------------------------------------------------
# routed factor exchange (SURVEY §2.3: the reference's block routing tables,
# ALSImpl.scala:39-41 [dep] — blocks exchange only the factor rows their
# ratings reference, not the whole opposite table)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RoutedSide:
    """Routed-exchange plan for one half-sweep.

    Replaces the full-table ``all_gather`` (every device receives the
    entire opposite factor table, (D-1)·opp_pb rows, regardless of need)
    with need-list routing: block d receives only the opposite rows its
    ratings reference, via one ``all_to_all`` of (D, r_max, k) send
    buffers.  Receive volume is D·r_max rows per device and SHRINKS as the
    mesh grows (per-block nnz drops, so need-lists thin out), where the
    all_gather's volume stays ~constant — exactly the scaling SURVEY §2.3
    prescribes for the 10M-user envelope.
    """

    send_idx: np.ndarray   # (D, D, r_max) int32: LOCAL factor rows source
    #                        block s sends to destination d; the diagonal
    #                        (s == d) and pad entries are spread over
    #                        s's guaranteed-zero strip — self-owned rows
    #                        never ride the collective
    idx: list              # per bucket: (D, rows_j, w_j) int32 into the
    #                        received table: off-block slots at
    #                        s*r_max + pos, self-owned slots at
    #                        D*r_max + local (the appended own shard)
    r_max: int             # max OFF-DIAGONAL route length (self excluded:
    #                        padding every route to a diagonal-dominated
    #                        r_max would ship the skew as zeros)
    recv_rows: int         # D*r_max + opp_pb (routed table incl. own shard)
    net_rows: int          # (D-1)*r_max — rows actually crossing ICI


def build_routing(side: SideLayout, opp: SideLayout,
                  n_blocks: int) -> RoutedSide:
    """Host-side routing tables: per destination block, the sorted unique
    opposite slots its ratings reference, split by owning source block.
    Self-owned rows are read straight from the local shard (appended after
    the exchanged stack), so the collective carries off-block needs only.
    Pure layout transform — gathered VALUES are identical to the gather
    path (same rows, same per-rating order), so routed and gathered sweeps
    agree bitwise."""
    D = n_blocks
    opp_pb = opp.per_block
    routes = [[None] * D for _ in range(D)]  # [src][dst] -> local rows
    r_max = 1
    for d in range(D):
        parts = [b[d].ravel() for b in side.idx]
        need = np.unique(np.concatenate(parts)) if parts else np.empty(
            0, np.int64)
        src = need // opp_pb
        loc = need % opp_pb
        for s in range(D):
            if s == d:
                continue  # self-owned rows come from the local shard
            routes[s][d] = loc[src == s]  # sorted (need is sorted)
            r_max = max(r_max, len(routes[s][d]))
    # every block ends in the zero strip; a route's unused tail and the
    # diagonal are spread over it like a bucket's pads
    send_idx = np.broadcast_to(
        opp_pb - _PAD_STRIP + _strip_slots(r_max), (D, D, r_max)).copy()
    for s in range(D):
        for d in range(D):
            if s == d:
                continue
            r = routes[s][d]
            send_idx[s, d, : len(r)] = r
    self_base = D * r_max  # own shard appended after the exchanged stack
    remapped = []
    for b in side.idx:
        out = np.empty_like(b)
        for d in range(D):
            g = b[d].astype(np.int64)
            s = g // opp_pb
            loc = g % opp_pb
            pos = np.empty_like(loc)
            for sb in range(D):
                m = s == sb
                if not m.any():
                    continue
                if sb == d:
                    pos[m] = self_base + loc[m] - sb * r_max  # net of the
                    # s*r_max term added below
                else:
                    pos[m] = np.searchsorted(routes[sb][d], loc[m])
            out[d] = (s * r_max + pos).astype(np.int32)
        remapped.append(out)
    return RoutedSide(send_idx=send_idx, idx=remapped, r_max=r_max,
                      recv_rows=D * r_max + opp_pb,
                      net_rows=(D - 1) * r_max)


_EXCHANGE_MODE_ENV = "FLINK_MS_ALS_EXCHANGE_MODE"


def _exchange_mode_choice() -> str:
    mode = os.environ.get(_EXCHANGE_MODE_ENV, "auto")
    if mode not in ("auto", "gather", "routed"):
        raise ValueError(
            f"{_EXCHANGE_MODE_ENV}={mode!r} must be auto|gather|routed"
        )
    return mode


def _exchange_plan(problem: BlockedProblem, D: int) -> dict:
    """-> {"u": RoutedSide|None, "i": RoutedSide|None} for a D-device mesh
    (None = full-table all_gather for that half-sweep).

    "auto" routes a half-sweep only when its need-lists actually receive
    fewer rows than the all_gather would; the dense/saturated regime
    (ML-20M: every block references nearly the whole 27k-item catalog)
    skips the routing build entirely on an nnz-density estimate.  Each
    half-sweep decides independently — a 10M-user catalog routes the
    user-factor exchange while the small item side keeps the gather.
    Plans are cached on the problem; the decision is logged with the
    per-device exchange-row accounting either way."""
    choice = _exchange_mode_choice()
    key = (D, choice)
    if key in problem.routing:
        return problem.routing[key]
    plan = {}
    for name, side, opp in (
        ("u", problem.u, problem.i),
        ("i", problem.i, problem.u),
    ):
        gather_rows = (D - 1) * opp.per_block
        if D == 1 or choice == "gather":
            plan[name] = None
            continue
        if choice == "auto" and problem.nnz / D >= 2.0 * opp.per_block * D:
            # each block's ratings reference ~the whole opposite catalog
            # (need saturates at 1-e^-x); routing can't beat the gather,
            # don't pay the host-side build
            print(
                f"[als] {name}-sweep exchange: gather ({gather_rows} "
                f"rows/device; need-lists saturated at nnz/D="
                f"{problem.nnz // D} vs {opp.per_block * D} opposite slots)"
            )
            plan[name] = None
            continue
        with tracing.phase("als.prepare.route"):
            routed = build_routing(side, opp, D)
        # ICI win condition: the all_to_all crosses (D-1)*r_max rows per
        # device vs the gather's (D-1)*opp_pb — route when the need-lists
        # are meaningfully thinner (margin for the extra take + concat)
        if choice == "routed" or routed.r_max < 0.8 * opp.per_block:
            print(
                f"[als] {name}-sweep exchange: routed all_to_all — "
                f"{routed.net_rows} rows/device over ICI vs {gather_rows} "
                f"all_gather (r_max={routed.r_max}, table "
                f"{routed.recv_rows} rows)"
            )
            plan[name] = routed
        else:
            print(
                f"[als] {name}-sweep exchange: gather ({gather_rows} "
                f"rows/device over ICI; routed would cross "
                f"{routed.net_rows})"
            )
            plan[name] = None
    problem.routing[key] = plan
    return plan


# ---------------------------------------------------------------------------
# device-side kernel
# ---------------------------------------------------------------------------

# upper bound on one bucket's gathered-factor transient (r·w·k f32); a
# bucket above it assembles in row chunks under lax.map so HBM holds one
# chunk's gather at a time.  Baked in at trace time (part of the sweep
# cache key via _assembly_chunk_bytes in _cached_sweep).
_ASSEMBLY_CHUNK_ENV = "FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES"


def _assembly_chunk_bytes() -> int:
    return int(os.environ.get(_ASSEMBLY_CHUNK_ENV, 2 << 30))


_LANES = 128  # one lane tile: what a minor dimension occupies in HBM


def _chunk_rows(r, w, k, y_itemsize, itemsize, how, implicit,
                per_chunk) -> Optional[int]:
    """Rows of an (r, w) bucket one assembly step takes, None where the
    whole bucket runs straight-line.  The peak transient of a step is the
    gather itself (at the EXCHANGE dtype's width), counted as what is live
    on the path ``how`` (``resolve_assembly``'s answer) takes:

    - "einsum": the values' bytes, plus in implicit mode the same-size
      solve-dtype yw intermediate (budgeted whether or not XLA fuses it
      into the contraction's operand);
    - "kernel": the rows as the take leaves them in HBM for the kernel to
      read, k values in whole lane tiles of 128, and no weighted copy (the
      kernel weights in VMEM).  At msd-ials' rank 64 that is the einsum
      pair's count to the byte, so nine of its eleven item buckets (1.09 GB
      of values, 2.17 GB in lane tiles) stay two steps.  Counted at their
      values' bytes they ran straight-line and lost: the takes of
      (2524, 1680) and (8743, 496) read 9.84 and 9.86 ns a row where each
      one's two halves read 3.95 and 3.97 (41.7 and 42.8 ms against 16.8
      and 17.2, +51 ms an iteration; the seven others read the same whole
      as halved; chip run, PERF.md section 6, PR 42).
      als-ml20m's largest, 658 MB of values at rank 50, is 1.68 GB.

    Where the step also solves (``per_chunk``) its (C, k, k) system and the
    factorization's intermediates are added.  A bucket above
    ``_assembly_chunk_bytes()`` is cut into
    the fewest steps that fit it, of equal size: steps of the largest size
    that fits left the last one nearly empty wherever a bucket came out
    just above a multiple of the limit, and msd-ials' item ladder does in
    nine buckets of eleven (two steps for 1.01-1.05 of one step's rows, the
    pad rows all gathering slot 0 and contracted like the others: 1.7538
    against 0.7200 s/iter, PERF.md section 6, PR 33).  The steps stay
    ragged: cut in whole lane tiles of rows (which would spare the solver's
    pad of a ragged batch) they compiled to a program whose item table no
    longer stays in fast memory, and the user half's takes went from 1.33
    ns a row to 3.96 and 9.9 (0.847 against 0.569 s/iter; chip run, PERF.md
    section 6, PR 42).  What the solver needs in whole tiles is padded
    after the contraction (``_bucket_normal_eqs``), not gathered."""
    if how == "kernel":
        row_bytes = w * -(-k // _LANES) * _LANES * y_itemsize
    else:
        row_bytes = w * k * (y_itemsize + (itemsize if implicit else 0))
    if per_chunk:
        row_bytes += 3 * k * k * itemsize
    limit = _assembly_chunk_bytes()
    if r * row_bytes <= limit:
        return None
    n_chunks = -(-r // max(int(limit // row_bytes), 1))
    return -(-r // n_chunks)


def _bucket_normal_eqs(y_all, idx, val, implicit, alpha, dtype,
                       precision, post=None, extra=None, platform=None,
                       lanes=False, unroll=False):
    """One bucket's (A, b): gather the opposite factors for each row's
    rating list and contract over the rating axis on the MXU — by the
    Pallas kernel or the einsum pair, as ``resolve_assembly`` answers for
    ``platform`` (the mesh's; None = einsum).

    No mask arrays exist: pad entries gather the opposite side's strip,
    whose factor rows are zero by construction, so every pad term
    vanishes through y itself (explicit A needs no weighting at all —
    one fewer (r, w, k) transient and multiply on the hot path).

    ``post`` (fused mode): a per-chunk (A, b, extra_chunk, in_scan=bool)
    -> out stage applied INSIDE each lax.map chunk — the fused
    assembly+solve path hands the solve in here so the bucket's
    (rows, k, k) normal equations never exist beyond one chunk's
    transient.  ``in_scan`` tells the stage whether it is being traced
    inside the lax.map body (where the Pallas solver must use its
    batch-major layout) or straight-line (lane-major compiles and is ~9%
    faster).  ``extra`` is an optional (rows, ...) operand sliced
    alongside idx/val (the per-slot counts).
    ``lanes`` (kernel path only: the Pallas solver takes the result).
    Without ``post``: return At (k, k, n), bt (k, n), the batch
    on the lanes and zero-padded to whole lane tiles as the Pallas solver
    reads it, instead of A (r, k, k), b (r, k).  A straight-line bucket's
    kernel writes it itself; inside the lax.map chunks the batch-major
    kernel stays (no lane-major operand may be laid out there, see
    ``_chol_solve``) and the bucket is transposed once after the map.
    With ``post``: each step's A and b are zero-padded to whole lane tiles
    of entities before ``post`` (the rows past the step's are systems of
    count 0 whose x is dropped after it).  ``cholesky_solve_batched`` pads
    a ragged batch by a copy of all of it; behind XLA's convolution that
    copy was the only pass between the contraction and the solver, behind
    a kernel it came after the pass that adds YᵀY and λ·reg (18.7 GB read
    and written once more over msd-ials' user half: ``als_solve_s`` 0.2068
    against 0.1519), and a pad here is fused into that pass (chip run,
    PERF.md section 6, PR 42).  The step's rows, and so its gather, stay
    as ``_chunk_rows`` cut them.
    Chunking is over the batch row axis only (the contraction axis w is
    untouched), so chunked and unchunked results are arithmetically
    identical per row.
    A cut bucket (``CutSide``) arrives as tuples: ``y_all`` the segments'
    tables, ``idx`` and ``val`` the bucket's piece for each.  Every piece
    is gathered and contracted as a bucket is and the pieces' A and b are
    summed in the solve dtype before ``post``: the same products in
    another order.  A step then takes the same rows of every piece.
    ``unroll``: the steps of a chunked bucket run one after the other in
    the program's text, not as a ``lax.map``.  For a piece that reads a
    segment of a table (``_assemble_normal_eqs``): the compiler keeps a
    61.5 MB segment in its fast memory through straight-line takes and
    not where a loop carries it (netflix-als-f100's segment 0 under
    ``lax.map``: its table in HBM and its takes in the 3.95 and 9.9 ns
    forms, the three segments without a chunked piece in the fast form;
    compiled for a described v5e, PERF.md section 6, PR 45)."""
    if not isinstance(y_all, (tuple, list)):
        y_all, idx, val = (y_all,), (idx,), (val,)
    r = idx[0].shape[0]
    k = y_all[0].shape[1]
    how = resolve_assembly(platform, y_all[0].dtype, dtype, k, precision)

    def compute(tables, idx_c, val_c, extra_c, in_scan=False):
        A = b = None
        for table, idx_s, val_s in zip(tables, idx_c, val_c):
            A_s, b_s = contract(table, idx_s, val_s, in_scan)
            A, b = (A_s, b_s) if A is None else (A + A_s, b + b_s)
        if post is None:
            return A, b
        rows = A.shape[0]
        n = -(-rows // _LANES) * _LANES
        if not lanes or n == rows:
            return post(A, b, extra_c, in_scan=in_scan)
        # whole lane tiles for the Pallas solver: XLA fuses this pad into
        # the pass that adds YᵀY and λ·reg (the docstring's ``lanes``)
        def pad(a):
            return jnp.pad(a, ((0, n - rows),) + ((0, 0),) * (a.ndim - 1))

        return post(pad(A), pad(b), pad(extra_c), in_scan=in_scan)[:rows]

    def contract(table, idx_c, val_c, in_scan):
        # the two scopes split als.assemble in a profile: the gather is
        # XLA's either way, the contraction is what `how` chose
        with jax.named_scope("als.gather"):
            # kernel path: out-of-range indices clip (there are none: pads
            # point at real slots, the strip's).  The default mode's fill is a
            # select over all of y, which XLA folds into the einsum's
            # operands but would run as a pass of its own before a kernel
            y = jnp.take(table, idx_c, axis=0,              # (r, w, k)
                         mode="clip" if how == "kernel" else None)
        # HIGHEST keeps f32 products (bf16 single-pass shifts the normal
        # equations enough to slow convergence at small lambda)
        with jax.named_scope("als.contract"):
            if how == "kernel":
                from . import assemble_pallas

                # implicit mode: the confidence weights are applied to the
                # gathered rows in VMEM (no als.weight operation is left)
                kw = dict(precision=precision, interpret=platform != "tpu",
                          alpha=alpha if implicit else None)
                if lanes and post is None and not in_scan:
                    A, b = assemble_pallas.assemble_bucket_lanes(y, val_c, **kw)
                else:
                    A, b = assemble_pallas.assemble_bucket(y, val_c, **kw)
            else:
                if implicit:
                    # the confidence weights and the weighted copy of y:
                    # what implicit mode adds to the einsum pair
                    with jax.named_scope("als.weight"):
                        wgt = (alpha * val_c).astype(dtype)  # pads: val 0 -> 0
                        t = (1.0 + alpha * val_c).astype(dtype)  # pads: y is 0
                        yw = y * wgt[..., None]
                    A = jnp.einsum("rwk,rwl->rkl", yw, y,
                                   precision=precision,
                                   preferred_element_type=dtype)
                else:
                    A = jnp.einsum("rwk,rwl->rkl", y, y, precision=precision,
                                   preferred_element_type=dtype)
                    t = val_c.astype(dtype)              # pads: val 0
                b = jnp.einsum("rwk,rw->rk", y, t, precision=precision,
                               preferred_element_type=dtype)
        return A, b

    C = _chunk_rows(r, sum(i.shape[1] for i in idx), k,
                    y_all[0].dtype.itemsize, np.dtype(dtype).itemsize,
                    how, implicit, post is not None)
    if C is None:
        return compute(y_all, idx, val, extra)
    # chunked: reshape to (n_chunks, C, ...) slabs and lax.map WITHOUT
    # batch_size, so the body genuinely computes C rows per step and only
    # one chunk's transients are ever live.  (lax.map's batch_size vmaps a
    # single-row body instead — in fused mode that traced the solve at
    # batch 1, padded every row to a 128-lane kernel tile, and the vmap
    # batched that padding into a 159 GB broadcast: the round-3 AOT OOM.)
    # Pad rows to a chunk multiple: pad gathers hit slot 0 and the padded
    # counts are 0, so the solve masks padded rows to zero and the slice
    # below discards them — per-row arithmetic is untouched.  Since the
    # steps are of equal size (_chunk_rows) that is fewer rows than the
    # bucket has steps, not most of a step.
    n_chunks = -(-r // C)
    r_pad = n_chunks * C

    def pad_rows(a):
        if r_pad == r:
            return a
        return jnp.pad(a, ((0, r_pad - r),) + ((0, 0),) * (a.ndim - 1))

    def steps(a):
        return pad_rows(a).reshape(n_chunks, C, a.shape[1])

    idx_c = tuple(steps(a) for a in idx)
    val_c = tuple(steps(a) for a in val)
    extra_c = None
    if extra is not None:
        extra_c = pad_rows(extra).reshape((n_chunks, C) + extra.shape[1:])

    def one_chunk(tables, args):
        if extra is None:
            return compute(tables, args[0], args[1], None, in_scan=True)
        return compute(tables, args[0], args[1], args[2], in_scan=True)

    operands = (idx_c, val_c) if extra is None else (idx_c, val_c, extra_c)
    if unroll:
        # the same steps, batch-major as under the map, in the text; one
        # jitted body, so the steps after the first are traced from it
        step = jax.jit(one_chunk)
        steps_out = [step(y_all, jax.tree.map(lambda t: t[c], operands))
                     for c in range(n_chunks)]
        out = jax.tree.map(lambda *t: jnp.concatenate(t, axis=0)[:r],
                           *steps_out)
    else:
        out = jax.tree.map(
            lambda t: t.reshape((r_pad,) + t.shape[2:])[:r],
            jax.lax.map(partial(one_chunk, y_all), operands),
        )
    if lanes and post is None:
        from .assemble_pallas import to_lanes

        return to_lanes(*out)
    return out


def _segment_table(y_all, s: int, seg_rows: int):
    """Segment ``s`` of the table ``y_all`` as a cut side's pieces address
    it: its ``seg_rows`` rows (the last segment's fewer, zeros after them)
    followed by a strip of ``_PAD_STRIP`` zero rows of its own.  A copy of
    the segment, under ``als.gather``: small enough for the compiler to keep
    where the takes read fastest (``table_segments``)."""
    lo = s * seg_rows
    hi = min(lo + seg_rows, y_all.shape[0] - _PAD_STRIP)
    with jax.named_scope("als.gather"):
        return jnp.pad(y_all[lo:hi],
                       ((0, seg_rows + _PAD_STRIP - (hi - lo)), (0, 0)))


def _assemble_normal_eqs(y_all, buckets, implicit, alpha, dtype,
                         precision="highest", platform=None, lanes=False,
                         seg_rows=None):
    """A_u = Σ w·y yᵀ and b_u = Σ t·y per slot, as batched MXU matmuls.

    y_all:   (n_slots_global, k) gathered opposite-side factor table
    buckets: list of (idx, val) with shapes (rows_j, w_j) — one entry
             per degree bucket, rows covering contiguous slot ranges
    returns A (per_block, k, k), b (per_block, k) in slot order.

    Explicit:  A = Σ y yᵀ,          b = Σ r·y    (normal equations of LS)
    Implicit:  A = Σ alpha·r·y yᵀ,  b = Σ (1+alpha·r)·y  (HKV; YtY added
               by caller)

    Pad entries have val 0 and idx = a slot of the opposite side's strip,
    whose factor rows are zero — every pad term vanishes through y or val.

    ``lanes``: the hand-off to the Pallas solver with nothing in between —
    At (k, k, n), bt (k, n), each bucket's entities on the lanes followed by
    its zero pad up to a whole lane tile, joined along the lanes (one copy;
    no relayout, no pad pass, no system for the strip:
    ``_solve_factors_lanes``).

    A cut side (``CutSide``, ``seg_rows`` its segments' rows): each bucket
    is a tuple of S (idx, val) pieces.  The loop over segments is the outer
    one, so that one segment's table (``_segment_table``) is live through
    all of the side's buckets and then dead (61-73 MB each at the cells'
    sizes, which the compiler then keeps in its fast memory in turn, where
    all S at once cannot lie), and a segment's (A, b) over every bucket is
    added to the sum of those before it in the solve dtype.
    """
    if seg_rows is None:
        buckets = [(bucket,) for bucket in buckets]
    k = y_all.shape[1]
    total = None
    for s in range(len(buckets[0])):
        with (contextlib.nullcontext() if seg_rows is None
              else jax.named_scope(f"als.segment{s}")):
            table = y_all if seg_rows is None else _segment_table(
                y_all, s, seg_rows)
            As, bs = [], []
            for pieces in buckets:
                idx, val = pieces[s]
                A, b = _bucket_normal_eqs(
                    table, idx, val, implicit, alpha, dtype, precision,
                    platform=platform, lanes=lanes,
                    unroll=seg_rows is not None,
                )
                As.append(A)
                bs.append(b)
            if not lanes:
                # zero systems for the block's strip (no bucket row covers
                # it); count==0 regularization keeps them PD and the solve
                # masks their results to zero, preserving the strip's zero
                # factor rows
                As.append(jnp.zeros((_PAD_STRIP, k, k), dtype))
                bs.append(jnp.zeros((_PAD_STRIP, k), dtype))
            part = (jnp.concatenate(As, axis=2 if lanes else 0),
                    jnp.concatenate(bs, axis=1 if lanes else 0))
            total = part if total is None else (total[0] + part[0],
                                                total[1] + part[1])
    return total


_FUSED_ENV = "FLINK_MS_ALS_FUSED"

# The materialised route holds a side's (per_block, k, k) normal equations
# twice (A, then A + λ·reg in the solver's padded layout) beside one
# assembly chunk's transients (FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES as
# _chunk_rows counts them), the ratings and both factor tables.  Up to a quarter
# of the device's memory the pair is half the chip at most and the rest has
# room; above it the sweep solves per chunk.  The two readings on either
# side of the constant (TPU v5e, 15.75 GiB): 8.2% of the memory
# (als-ml20m's users, 1.38 GB) runs 9.8% slower per chunk (0.142589 against
# 0.129878 s/iter, PR 30) and 55% (msd-ials' users, 9.36 GB) is refused
# by the compiler when materialised (27.14 GiB of 15.75, PR 33).
_MATERIALISE_SHARE = 0.25


def solves_per_chunk(rows: int, k: int, itemsize: int,
                     memory_bytes: Optional[int]) -> bool:
    """Whether a side of ``rows`` slots per device solves each assembly
    chunk inside the assembly ``lax.map`` (so that its (rows, k, k)
    normal-equation tensor never exists and the half-sweep's peak stops
    scaling with the catalog) or materialises the tensor and solves it in
    one batch.  FLINK_MS_ALS_FUSED forces it for both sides, ``1`` per
    chunk and ``0`` materialised; unset, the tensor's bytes are held
    against ``memory_bytes``, one device's memory as the runtime reports it
    (``_MATERIALISE_SHARE``), and a runtime that reports none (the CPU)
    keeps the tensor.  The per-chunk route keeps the batch-major hand-off
    (A (r, k, k) out of the kernel or the einsums, laid out for the solver
    by XLA, one solver body a bucket): where both fit, the materialised
    route is the faster (PERF.md section 7)."""
    choice = os.environ.get(_FUSED_ENV, "")
    if choice not in ("", "0", "1"):
        raise ValueError(
            f"{_FUSED_ENV}={choice!r}: expected 0, 1 or unset")
    if choice:
        return choice == "1"
    if not memory_bytes:
        return False
    return rows * k * k * itemsize > _MATERIALISE_SHARE * memory_bytes


def _routes(problem: "BlockedProblem", config: "ALSConfig",
            mesh: Mesh) -> Dict[str, bool]:
    """``solves_per_chunk`` for each side of one fit on ``mesh``."""
    memory = device_memory(mesh.devices.flat[0])
    itemsize = np.dtype(config.dtype).itemsize
    return {name: solves_per_chunk(side.per_block, config.num_factors,
                                   itemsize, memory)
            for name, side in (("u", problem.u), ("i", problem.i))}


# Two solvers.  "pallas" on a TPU: one VMEM-resident pass per tile, 7.2 ms
# of the 129.9 ms iteration at the ML-20M shape (PERF.md section 5), where
# XLA's own lax.linalg lowering is a device while-loop of dynamic slices
# that streams the whole (n, k, k) tensor per elimination step (492.6
# against 62.7 ms/iter at a 5M-nnz, k=50 probe; 2026-07-31, earlier
# installation, not reproduced).  "lax" everywhere else: LAPACK-backed on
# the host.  Naming "pallas" on a CPU runs the chip's kernel interpreted
# (chip_smoke.py --tiny, tests/test_cholesky_pallas.py).
_SOLVERS = ("auto", "pallas", "lax")


def resolve_solver(platform: Optional[str]) -> str:
    """The solver a fit on `platform` runs: FLINK_MS_ALS_SOLVER when it
    names one, else "pallas" on a TPU and "lax" everywhere else."""
    choice = os.environ.get("FLINK_MS_ALS_SOLVER", "auto")
    if choice not in _SOLVERS:
        raise ValueError(
            f"FLINK_MS_ALS_SOLVER={choice!r}: expected one of "
            + " | ".join(_SOLVERS)
        )
    if choice == "auto":
        return "pallas" if platform == "tpu" else "lax"
    return choice


def resolve_exchange(exchange_dtype: Optional[str],
                     platform: Optional[str]) -> Optional[str]:
    """The factor-exchange dtype an "auto" config resolves to on
    `platform` (explicit values and None pass through).  bfloat16 on TPU
    (half the bytes moved; its speed and RMSE delta are in the comment on
    ALSConfig.exchange_dtype); full
    precision everywhere else — the CPU baseline/reference paths must
    not silently change numerics."""
    if exchange_dtype == "auto":
        return "bfloat16" if platform == "tpu" else None
    return exchange_dtype


# The widest rank the assembly kernel takes: the top of what its tiles and
# the Pallas solver's state (``assemble_pallas.tile_sizes``,
# ``cholesky_pallas.solver_tile``).  Up to 64 the cells at ranks 50 and 64
# priced it (PRs 26, 30, 42).  From 65 to 128 two chip readings at rank 100
# set it (``netflix-als-f100.retrain``, 480,189 x 17,770, 99,072,112
# ratings, one seed, 20 s windows, TPU v5e, PR 44): the kernel 1.534509
# s/iter and 7.23 GB held, the einsum pair 1.936428 and 10.87 GB.
_KERNEL_MAX_RANK = 128


def resolve_assembly(platform: Optional[str], y_dtype, dtype, k: int,
                     precision: str = "highest") -> str:
    """How a sweep's buckets contract their gathered rows: "kernel"
    (``assemble_pallas.assemble_bucket``: A and b from one read of y, no
    relayout copy) or "einsum" (the pair XLA schedules itself).  The kernel
    engages where a chip run priced it — a TPU, an f32
    exchange and solve, full-f32 or one-pass products, rank 10-128
    (``_KERNEL_MAX_RANK``, with the two readings at rank 100 that set it
    above 64) — and everything else keeps the einsum pair
    unchanged: the bf16 exchange (``als_train``'s default on a TPU, timed
    by the cell ``als-ml20m-bf16x.retrain-bf16x``), three-pass products,
    ranks above 128 and every CPU fit.  The mode does not enter:
    implicit feedback's weights are one multiply in the kernel's VMEM
    (PERF.md section 6, PR 42).  It holds no width: on the chip the kernel
    is ahead at every width of the ML-20M ladder, w = 24 included."""
    if platform != "tpu":
        return "einsum"
    if jnp.dtype(y_dtype) != jnp.float32 or jnp.dtype(dtype) != jnp.float32:
        return "einsum"
    if precision == "high":
        return "einsum"  # Mosaic's matmul has no three-pass mode
    return "kernel" if k <= _KERNEL_MAX_RANK else "einsum"


def _exchange_and_assembly(config: "ALSConfig", platform: Optional[str]):
    """-> (the exchange dtype or None for the solve dtype's, "kernel" |
    "einsum") of a fit of ``config`` on ``platform``."""
    resolved = resolve_exchange(config.exchange_dtype, platform)
    exchange = jnp.dtype(resolved) if resolved else None
    return exchange, resolve_assembly(
        platform, exchange or config.dtype, config.dtype, config.num_factors,
        config.assembly_precision)


def exchange_report(config: "ALSConfig", mesh: Mesh) -> str:
    """``exchange <dtype>, <Pallas kernel | einsum pair>``: what the opposite
    side's rows are gathered in and what contracts them in a fit of
    ``config`` on ``mesh``, for the trainer's report line."""
    exchange, how = _exchange_and_assembly(config,
                                           mesh.devices.flat[0].platform)
    return (f"exchange {jnp.dtype(exchange or config.dtype).name}, "
            + ("Pallas kernel" if how == "kernel" else "einsum pair"))


def _segments(problem: "BlockedProblem", config: "ALSConfig",
              mesh: Mesh) -> Dict[str, int]:
    """``table_segments`` of the table each half-sweep of one fit on
    ``mesh`` gathers from (the user half reads the item table).  1 on a
    mesh of more than one device (the gathered or routed table of D blocks
    is not cut: no cell, no reading) and for the einsum pair on a TPU (the
    bf16 exchange, ``als_train``'s default there; its cell,
    ``als-ml20m-bf16x.retrain-bf16x``, has tables that fit whole, so what
    its convolutions need of the fast memory beside a segment is unread)."""
    device = mesh.devices.flat[0]
    exchange, how = _exchange_and_assembly(config, device.platform)
    if num_blocks(mesh) > 1 or (device.platform == "tpu" and how != "kernel"):
        return {"u": 1, "i": 1}
    from .assemble_pallas import _lanes_vmem_limit

    k = config.num_factors
    itemsize = (exchange or np.dtype(config.dtype)).itemsize
    return {name: table_segments(opp.per_block, k, itemsize,
                                 fast_memory(device), _lanes_vmem_limit(k))
            for name, opp in (("u", problem.i), ("i", problem.u))}


def _cuts(problem: "BlockedProblem", config: "ALSConfig",
          mesh: Mesh) -> Dict[str, Optional[CutSide]]:
    """Each side's ``CutSide`` for one fit on ``mesh``, None where its
    half-sweep gathers from the whole table (``_segments`` says 1).  Host
    work, done once a problem and segment count (phase
    ``als.prepare.segment``) and kept on the problem."""
    out = {}
    for name, segments in _segments(problem, config, mesh).items():
        if segments > 1 and (name, segments) not in problem.cuts:
            side, opp = ((problem.u, problem.i) if name == "u"
                         else (problem.i, problem.u))
            with tracing.phase("als.prepare.segment"):
                problem.cuts[name, segments] = cut_side(side, opp, segments)
        out[name] = problem.cuts[name, segments] if segments > 1 else None
    return out


def _calls(side, cut: Optional[CutSide], per_chunk: bool):
    """(rows, width) of every ``_bucket_normal_eqs`` call one half-sweep
    makes, bucket by bucket: the bucket; on a cut side each of its pieces
    (materialised route, a call a piece) or their widths' sum (per-chunk
    route, one call over all of a bucket's pieces)."""
    if cut is None:
        return [[(r, w)] for r, w in zip(side.rows, side.widths)]
    if per_chunk:
        return [[(r, sum(ws))] for r, ws in zip(side.rows, cut.widths)]
    return [[(r, w) for w in ws] for r, ws in zip(side.rows, cut.widths)]


_WHOLE = {"u": None, "i": None}   # no side cut: ``_cuts`` of most fits


def _solver_tiles(problem: "BlockedProblem", config: "ALSConfig",
                  platform: Optional[str], per_chunk: Dict[str, bool],
                  cuts: Dict[str, Optional[CutSide]] = _WHOLE
                  ) -> Dict[str, int]:
    """{solver layout: tile} over the Pallas solver's entries one compiled
    sweep runs (``cholesky_pallas.solver_tile``): batch-major where a side
    on the per-chunk route solves inside its ``lax.map`` steps, lane-major
    everywhere else; empty under the ``lax`` solver."""
    from .cholesky_pallas import solver_tile

    if resolve_solver(platform) != "pallas":
        return {}
    k = config.num_factors
    exchange, how = _exchange_and_assembly(config, platform)
    itemsize = np.dtype(config.dtype).itemsize
    y_itemsize = exchange.itemsize if exchange else itemsize
    layouts = set()
    for name, side in (("u", problem.u), ("i", problem.i)):
        for bucket in _calls(side, cuts[name], per_chunk[name]):
            for r, w in bucket:
                in_scan = per_chunk[name] and _chunk_rows(
                    r, w, k, y_itemsize, itemsize, how, config.implicit,
                    True) is not None
                layouts.add("batch_major" if in_scan else "lane_major")
    return {layout: solver_tile(k, layout)[0] for layout in sorted(layouts)}


def _log_assembly(problem: "BlockedProblem", how: str, lanes: bool,
                  k: int, per_chunk: Dict[str, bool],
                  tiles: Optional[Dict[str, int]] = None,
                  cuts: Dict[str, Optional[CutSide]] = _WHOLE, *,
                  exchange: str) -> None:
    """The static choices of one compiled sweep, per side: the table its
    gather reads, whole or in segments (``table_segments``); the solve's route
    (``solves_per_chunk``) beside the bytes its normal equations take per
    device, how many buckets the kernel takes and their share of the padded
    ratings, and how many of them hand A to the solver lane-major from the
    kernel itself (with ``lanes`` on the materialised route, every bucket
    ``_chunk_rows`` leaves straight-line, on a cut side in every piece; the
    others are transposed after their lax.map) and their share of the
    entities; then the rank, the assembly's form and ``tiles``
    (``_solver_tiles``); last ``exchange``, the dtype the opposite side's
    rows are gathered in."""
    parts = []
    for name, side, opp in (("u", problem.u, problem.i),
                            ("i", problem.i, problem.u)):
        calls = _calls(side, cuts[name], per_chunk[name])
        padded = sum(r * w for bucket in calls for r, w in bucket)
        on = len(side.widths) if how == "kernel" else 0
        direct = [bucket[0][0] for bucket in calls
                  if lanes and not per_chunk[name]
                  and all(_chunk_rows(r, w, k, 4, 4, how, False, False) is None
                          for r, w in bucket)]
        cut = cuts[name]
        table = (f"{cut.segments} segments of {cut.seg_rows}" if cut
                 else f"1 segment of {opp.per_block}")
        parts.append(f"{name}-sweep solve "
                     f"{'per chunk' if per_chunk[name] else 'materialised'} "
                     f"({side.per_block * k * k * 4 / 1e9:.2f} GB of normal "
                     f"equations), table in {table} rows, "
                     f"kernel on {on} of {len(side.widths)} "
                     f"buckets ({100.0 if on else 0.0:.1f}% of {padded} "
                     f"padded ratings), lane-major hand-off on {len(direct)} "
                     f"({100.0 * sum(direct) / sum(side.rows):.1f}% of "
                     f"{sum(side.rows)} entities)")
    form = "Pallas kernel" if how == "kernel" else "einsum pair"
    solver = "".join(f", {tile} {layout.replace('_', '-')}"
                     for layout, tile in (tiles or {}).items())
    print("[als] assembly: " + ", ".join(parts) + "; einsum pair elsewhere"
          + f"; rank {k}: {form}" + (", solver tile" + solver[1:] if solver
                                     else "") + f"; exchange {exchange}")


def _chol_solve(A, b, platform: Optional[str] = None, in_scan=False,
                shared=False):
    if resolve_solver(platform) == "pallas":
        from .cholesky_pallas import cholesky_solve_batched

        # in_scan (the fused per-chunk solve inside lax.map): the kernel's
        # lane-major operand relayout is uncompilable there (degenerate-
        # dim copy, 62.5 GB AOT OOM) -- force the batch-major variant
        layout = "batch_major" if in_scan else "lane_major"
        return cholesky_solve_batched(
            A, b, interpret=platform != "tpu", layout=layout, shared=shared
        ).astype(A.dtype)
    L = jax.lax.linalg.cholesky(A)
    x = jax.lax.linalg.triangular_solve(
        L, b[..., None], left_side=True, lower=True
    )
    return jax.lax.linalg.triangular_solve(
        L, x, left_side=True, lower=True, transpose_a=True
    )[..., 0]


def _reg_diagonal(counts, lam, weighted_reg):
    """λ·reg, and 1 for an empty row (a padding entity, an id with no
    ratings): the identity system keeps Cholesky PD, the caller zeroes x."""
    reg = counts if weighted_reg else jnp.ones_like(counts)
    return lam * reg + jnp.where(counts > 0, 0.0, 1.0)


def _solve_factors(A, b, counts, lam, weighted_reg, dtype,
                   platform: Optional[str] = None, in_scan=False,
                   shared=False):
    """Batched Cholesky solve of (A + λ·reg·I) x = b with empty rows masked.
    ``shared``: one of many such calls in the program (a bucket of a side
    solved per chunk), which the Pallas solver then traces as one body."""
    k = A.shape[-1]
    diag = _reg_diagonal(counts, lam, weighted_reg)
    A = A + diag[:, None, None] * jnp.eye(k, dtype=dtype)
    x = _chol_solve(A, b, platform, in_scan=in_scan, shared=shared)
    return jnp.where((counts > 0)[:, None], x, 0.0)


def _solve_factors_lanes(At, bt, counts, rows, lam, weighted_reg,
                         platform: Optional[str]):
    """``_solve_factors`` for ``_assemble_normal_eqs(lanes=True)``' output,
    ``rows`` the buckets' entity counts: the same system per entity, with
    the diagonal added inside the solver's tile.  Only the (n,) diagonal
    goes out to the kernels' lane layout and only x (k, n) comes back from
    it; a bucket's pad lanes are identity systems, and the block's strip,
    which no bucket covers, is the zero rows appended here."""
    from .assemble_pallas import LANES
    from .cholesky_pallas import cholesky_solve_lanes

    diag = _reg_diagonal(counts, lam, weighted_reg)
    d, picks, slot, lane = [], [], 0, 0
    for r in rows:
        pad = -r % LANES
        d.append(jnp.pad(diag[slot:slot + r], (0, pad), constant_values=1.0))
        picks.append(slice(lane, lane + r))
        slot, lane = slot + r, lane + r + pad
    x = cholesky_solve_lanes(At, bt, jnp.concatenate(d),
                             interpret=platform != "tpu")
    x = jnp.concatenate([x[:, p] for p in picks]
                        + [jnp.zeros((x.shape[0], _PAD_STRIP), x.dtype)],
                        axis=1)
    return jnp.where((counts > 0)[:, None], x.T, 0.0)


def _flat_side_args(side: SideLayout, dtype, routed=None, cut=None):
    """Device-arg flattening of one side: bucket (idx, val) pairs then the
    count; a routed half-sweep appends its send plan and swaps the idx
    arrays for their received-table remapping; a cut side (``CutSide``)
    has S pairs a bucket, its pieces, in place of the bucket's one."""
    out = []
    for j in range(len(side.widths)):
        if cut is not None:
            for idx, val in zip(cut.idx[j], cut.val[j]):
                out += [idx, val.astype(dtype)]
            continue
        out += [
            routed.idx[j] if routed is not None else side.idx[j],
            side.val[j].astype(dtype),
        ]
    out.append(side.count.astype(dtype))
    if routed is not None:
        out.append(routed.send_idx)
    return out


def _make_sweep(problem: BlockedProblem, config: ALSConfig, mesh: Mesh):
    """Build the jitted full-fit function: fori_loop over iterations, each
    iteration = user half-sweep then item half-sweep, all inside one
    shard_map so factor exchange rides ICI — a full-table ``all_gather``,
    or a need-list-routed ``all_to_all`` per the problem's exchange plan."""
    k = config.num_factors
    lam = config.lambda_
    implicit = config.implicit
    alpha = config.alpha
    weighted = config.weighted_reg and not implicit
    dtype = config.dtype
    n_u_buckets = len(problem.u.widths)
    n_i_buckets = len(problem.i.widths)
    platform = mesh.devices.flat[0].platform
    plan = _exchange_plan(problem, num_blocks(mesh))

    exchange_dtype, how = _exchange_and_assembly(config, platform)
    # the kernel path hands A to the Pallas solver in the solver's own
    # layout (the per-chunk route below solves batch-major)
    lanes = how == "kernel" and resolve_solver(platform) == "pallas"
    per_chunk = _routes(problem, config, mesh)
    cuts = _cuts(problem, config, mesh)
    if platform == "tpu":
        _log_assembly(problem, how, lanes, k, per_chunk,
                      _solver_tiles(problem, config, platform, per_chunk,
                                    cuts), cuts,
                      exchange=jnp.dtype(exchange_dtype or dtype).name)

    def half_sweep(y_shard, flat, routed: bool, fused: bool,
                   cut: Optional[CutSide], rows: Tuple[int, ...]):
        # y_shard: (1, opp_pb, k) this device's shard of the opposite factors
        # the three named scopes are how a profile tells the sweep's device
        # time apart (they write metadata only; the program is the same)
        if routed:
            *bucket_args, counts, send_idx = flat
        else:
            *bucket_args, counts = flat
        with jax.named_scope("als.exchange"):
            y_send = y_shard[0]
            if exchange_dtype is not None:
                # cast BEFORE the collective: the exchange moves half the
                # bytes over ICI and every downstream gather reads half the
                # bytes from HBM; accumulation stays in the solve dtype
                y_send = y_send.astype(exchange_dtype)
            if routed:
                # need-list exchange: send each destination only the
                # off-block rows its ratings reference (pad/diagonal rows
                # are the strip's -> zeros); the received (D, r_max, k)
                # stack plus the device's OWN shard is the gather table,
                # with idx arrays pre-remapped (off-block: s*r_max + pos;
                # self: D*r_max + local)
                picked = jnp.take(y_send, send_idx[0], axis=0)  # (D, r_max, k)
                recv = jax.lax.all_to_all(
                    picked, BLOCK_AXIS, split_axis=0, concat_axis=0
                ).reshape(-1, k)
                y_all = jnp.concatenate([recv, y_send], axis=0)
            else:
                y_all = jax.lax.all_gather(
                    y_send, BLOCK_AXIS, axis=0, tiled=True)
        buckets = [
            (bucket_args[2 * j][0], bucket_args[2 * j + 1][0])
            for j in range(len(bucket_args) // 2)
        ]
        seg_rows = None
        if cut is not None:
            # S pieces a bucket, each against its segment of the table
            S, seg_rows = cut.segments, cut.seg_rows
            buckets = [tuple(buckets[j:j + S])
                       for j in range(0, len(buckets), S)]
        yty = None
        if implicit:
            # als.gram: what implicit mode adds outside the buckets, the
            # (k, k) Gramian of the whole other side and its add to A
            with jax.named_scope("als.assemble"), jax.named_scope("als.gram"):
                yty = jax.lax.psum(
                    jnp.einsum("nk,nm->km", y_shard[0], y_shard[0],
                               precision=config.assembly_precision,
                               preferred_element_type=dtype),
                    BLOCK_AXIS,
                )
        if fused:
            # per-bucket fused assembly+solve: bucket outputs are
            # contiguous slot ranges, so each bucket's factor rows are
            # solved straight out of its assembly chunks and concatenated
            # in slot order — the full (per_block, k, k) tensor never
            # exists.  The block's strip gets its zero rows appended
            # explicitly (the unfused path routes them through zero
            # systems + the count mask).
            def solve_chunk(A, bb, cnt, in_scan=False):
                with jax.named_scope("als.solve"):
                    if yty is not None:
                        with jax.named_scope("als.gram"):
                            A = A + yty[None, :, :]
                    return _solve_factors(A, bb, cnt, lam, weighted, dtype,
                                          platform, in_scan=in_scan,
                                          shared=True)

            xs = []
            off = 0
            if cut is not None:
                # every step sums a bucket's pieces before it solves, so
                # all S tables are live through the half-sweep
                with jax.named_scope("als.assemble"):
                    y_all = tuple(_segment_table(y_all, s, seg_rows)
                                  for s in range(S))
                buckets = [tuple(zip(*pieces)) for pieces in buckets]
            for (idx_b, val_b), rows_j in zip(buckets, rows):
                with jax.named_scope("als.assemble"):
                    xs.append(_bucket_normal_eqs(
                        y_all, idx_b, val_b, implicit, alpha, dtype,
                        config.assembly_precision,
                        post=solve_chunk, extra=counts[0][off:off + rows_j],
                        platform=platform, lanes=lanes,
                    ))
                off += rows_j
            xs.append(jnp.zeros((_PAD_STRIP, k), dtype))
            return jnp.concatenate(xs, axis=0)[None]
        with jax.named_scope("als.assemble"):
            A, b = _assemble_normal_eqs(
                y_all, buckets, implicit, alpha, dtype,
                precision=config.assembly_precision, platform=platform,
                lanes=lanes, seg_rows=seg_rows,
            )
        with jax.named_scope("als.solve"):
            if lanes:
                if implicit:
                    # pad lanes become YᵀY + I: still positive definite,
                    # and their x is discarded as before
                    with jax.named_scope("als.gram"):
                        A = A + yty[:, :, None]
                x = _solve_factors_lanes(
                    A, b, counts[0], rows, lam, weighted, platform)
            else:
                if implicit:
                    with jax.named_scope("als.gram"):
                        A = A + yty[None, :, :]
                x = _solve_factors(A, b, counts[0], lam, weighted, dtype,
                                   platform)
        return x[None]  # (1, per_block, k)

    # (idx, val) pairs a side: one a bucket, S a bucket of a cut side
    n_u_pairs, n_i_pairs = (
        n * (cuts[name].segments if cuts[name] else 1)
        for name, n in (("u", n_u_buckets), ("i", n_i_buckets)))
    n_u_args = 2 * n_u_pairs + 1 + (1 if plan["u"] is not None else 0)

    def fit_body(iterations, uf, itf, *flat):
        u_flat, i_flat = flat[:n_u_args], flat[n_u_args:]

        def one_iter(_, carry):
            uf, itf = carry
            with jax.named_scope("als.user_half"):
                uf = half_sweep(itf, u_flat, routed=plan["u"] is not None,
                                fused=per_chunk["u"], cut=cuts["u"],
                                rows=problem.u.rows)
            with jax.named_scope("als.item_half"):
                itf = half_sweep(uf, i_flat, routed=plan["i"] is not None,
                                 fused=per_chunk["i"], cut=cuts["i"],
                                 rows=problem.i.rows)
            return uf, itf

        # dynamic trip count (lowers to while_loop): one compiled program
        # serves any --iterations value
        return jax.lax.fori_loop(0, iterations, one_iter, (uf, itf))

    spec3 = P(BLOCK_AXIS, None, None)
    spec2 = P(BLOCK_AXIS, None)
    flat_specs = (
        (spec3,) * (2 * n_u_pairs) + (spec2,)
        + ((spec3,) if plan["u"] is not None else ())  # send_idx
        + (spec3,) * (2 * n_i_pairs) + (spec2,)
        + ((spec3,) if plan["i"] is not None else ())
    )
    sharded_fit = shard_map(
        fit_body,
        mesh=mesh,
        in_specs=(P(), spec3, spec3) + flat_specs,
        out_specs=(spec3, spec3),
        check_vma=False,
    )
    return _counted(jax.jit(sharded_fit))


def _counted(jitted):
    """``jitted(iterations, *args)`` behind the registry's
    ``tpums_als_iterations_total``: iterations enqueued, not awaited (their
    device time lies under the als.* scopes of a profile).  ``lower`` is the
    jitted function's own, for whoever compiles ahead of time."""
    def fit(iterations, *args):
        out = jitted(iterations, *args)
        if not isinstance(iterations, jax.core.Tracer):
            # a device scalar is fetched once and then kept by jax
            obs_metrics.get_registry().counter(
                "tpums_als_iterations_total").inc(int(iterations))
        return out

    fit.lower = jitted.lower
    return fit


_SWEEP_CACHE: "dict" = {}
_SWEEP_CACHE_MAX = 8  # bounded: long-lived retrain loops see fresh nnz_pad
                      # shapes per refresh and would otherwise leak executables


def _cached_sweep(problem: BlockedProblem, config: ALSConfig, mesh: Mesh):
    """One compiled program per (layout shapes, config, mesh) — repeat fits
    (benchmark loops, retrain cycles) skip retracing."""
    key = (
        mesh,
        problem.n_blocks,
        problem.u.per_block,
        problem.i.per_block,
        problem.u.widths,
        problem.u.rows,
        problem.i.widths,
        problem.i.rows,
        config.num_factors,
        config.lambda_,
        config.implicit,
        config.alpha,
        config.weighted_reg,
        str(config.dtype),
        config.assembly_precision,
        config.exchange_dtype,
        # the exchange plan changes arg shapes and the collective: key by
        # each half-sweep's mode + received-table size
        tuple(
            (name, None if r is None else r.r_max)
            for name, r in sorted(
                _exchange_plan(problem, num_blocks(mesh)).items()
            )
        ),
        # env overrides are baked in at trace time, so they key the
        # executable; an unknown solver name raises here, before any trace
        resolve_solver(mesh.devices.flat[0].platform),
        _assembly_chunk_bytes(),
        tuple(sorted(_routes(problem, config, mesh).items())),
        # a cut side's pieces are other argument shapes
        tuple((name, cut and (cut.seg_rows, tuple(cut.widths)))
              for name, cut in sorted(_cuts(problem, config, mesh).items())),
    )
    fn = _SWEEP_CACHE.pop(key, None)
    if fn is None:
        fn = _make_sweep(problem, config, mesh)
    _SWEEP_CACHE[key] = fn  # re-insert: dict order gives LRU eviction
    while len(_SWEEP_CACHE) > _SWEEP_CACHE_MAX:
        del _SWEEP_CACHE[next(iter(_SWEEP_CACHE))]
    return fn


# ---------------------------------------------------------------------------
# iteration-boundary staging (the reference's setTemporaryPath,
# ALSImpl.scala:42-44: materialize loop intermediates to disk instead of one
# fused plan — here it doubles as training checkpoint/resume, SURVEY.md §5)
# ---------------------------------------------------------------------------

_STAGE_RE = re.compile(r"^iter_(\d+)\.npz$")


def _ties_ascending(ix: np.ndarray, v: np.ndarray) -> np.ndarray:
    """``v`` with every run of one list (last axis) whose ``ix`` are equal
    sorted ascending: what a bucket's ``val`` is whichever way the fill's
    sort left two ratings of one (row, slot) pair."""
    tie = ix[..., 1:] == ix[..., :-1]
    if not tie.any():
        return v
    member = np.zeros(ix.shape, bool)     # tied to a neighbour of its list
    member[..., 1:] = tie
    follows = member.copy()               # ... to the one before it
    member[..., :-1] |= tie
    at = np.flatnonzero(member)
    run = np.cumsum(~follows.reshape(-1)[at])
    v = v.copy()
    flat = v.reshape(-1)
    flat[at] = flat[at[np.lexsort((flat[at], run))]]
    return v


def _staging_meta(problem: "BlockedProblem", config: "ALSConfig",
                  init, platform: "Optional[str]" = None) -> dict:
    """Identity of a training run; a snapshot from a different dataset,
    problem, config, dtype, or starting point must not be resumed.
    ``platform`` resolves the "auto" exchange dtype: the meta must record
    the NUMERICS the run actually used, so a bf16-on-TPU snapshot cannot
    silently resume as an f32-on-CPU continuation (or vice versa)."""
    if init is None:
        init_id = "seed"
    else:
        h = hashlib.sha1()
        h.update(np.ascontiguousarray(init[0]).tobytes())
        h.update(np.ascontiguousarray(init[1]).tobytes())
        init_id = h.hexdigest()
    # the actual rating data matters too: same-shaped re-exports of fresh
    # data must retrain, not resume (bucket arrays cover ids, values, layout).
    # Slots are hashed with every block's strip folded back to the one slot
    # a block ended in before the strip, and pads aimed at block 0's as they
    # were then: the identity does not depend on _PAD_STRIP, and a snapshot
    # written before the strip keeps resuming (its factors are in dense-id
    # order, which no layout touches).  Ratings of one (user, item) pair are
    # hashed in ascending order: the identity is the ratings', whatever
    # order the fill's sort, this host's or another's, left them in
    fold = _PAD_STRIP - 1
    opp_pb = problem.i.per_block
    idx = [
        np.where(ix % opp_pb >= opp_pb - _PAD_STRIP, opp_pb - _PAD_STRIP,
                 ix - ix // opp_pb * fold).astype(np.int32)
        for ix in problem.u.idx
    ]
    perm = problem.u.perm - problem.u.perm // problem.u.per_block * fold
    # (ties are read from the slots as laid out, where a list's pads differ)
    val = [_ties_ascending(ix, v)
           for ix, v in zip(problem.u.idx, problem.u.val)]
    hd = hashlib.sha1()
    for a in [perm, problem.user_ids, problem.item_ids] + idx + val:
        hd.update(np.ascontiguousarray(a).tobytes())
    return {
        "data": hd.hexdigest(),
        "num_factors": config.num_factors,
        "lambda": config.lambda_,
        "implicit": config.implicit,
        "alpha": config.alpha,
        "weighted_reg": config.weighted_reg,
        "assembly_precision": config.assembly_precision,
        "exchange_dtype": resolve_exchange(config.exchange_dtype, platform),
        "seed": config.seed,
        "dtype": str(np.dtype(config.dtype)),
        "init": init_id,
        "n_users": problem.n_users,
        "n_items": problem.n_items,
        "nnz": problem.nnz,
        "n_blocks": problem.n_blocks,
    }


def save_staged(path: str, iteration: int, uf: np.ndarray, itf: np.ndarray,
                meta: dict, keep: int = 2) -> str:
    """Atomically write one iteration snapshot under `path`.

    The staging dir is scratch space for the *current* run (the reference's
    temporaryPath semantics), so everything outside the trailing `keep`
    window ending at `iteration` is pruned — including stale higher-numbered
    snapshots left by a previous longer run."""
    os.makedirs(path, exist_ok=True)
    out = os.path.join(path, f"iter_{iteration:05d}.npz")
    tmp = out + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, user_factors=uf, item_factors=itf,
                 meta=np.frombuffer(json.dumps(meta).encode(), dtype=np.uint8))
    os.replace(tmp, out)
    for name in os.listdir(path):
        m = _STAGE_RE.match(name)
        if m and iteration - keep < int(m.group(1)) <= iteration:
            continue
        if not (m or name.endswith(".npz.tmp")):  # orphans of a mid-write kill
            continue
        try:
            os.remove(os.path.join(path, name))
        except OSError:
            pass
    return out


def load_staged(path: str, meta: dict, max_iteration: Optional[int] = None):
    """Latest matching snapshot -> (iteration, uf, itf), else None.
    Corrupt or mismatching snapshots are skipped (newest first); snapshots
    beyond `max_iteration` are ignored so re-running with fewer iterations
    does not return an over-trained model."""
    if not os.path.isdir(path):
        return None
    snaps = sorted(
        (int(m.group(1)), m.string) for m in
        (_STAGE_RE.match(n) for n in os.listdir(path)) if m
    )
    for iteration, name in reversed(snaps):
        if max_iteration is not None and iteration > max_iteration:
            continue
        try:
            with np.load(os.path.join(path, name)) as z:
                saved = json.loads(bytes(z["meta"]).decode())
                # snapshots written before the assembly_precision field
                # existed were produced with hard-coded HIGHEST — backfill
                # so they keep resuming
                saved.setdefault("assembly_precision", "highest")
                # ... and before the exchange_dtype field (full precision)
                saved.setdefault("exchange_dtype", None)
                if saved != meta:
                    continue
                return iteration, z["user_factors"], z["item_factors"]
        except Exception:
            continue
    return None


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ALSModel:
    """Trained factors with the raw-id mapping (dense row i of
    `user_factors` belongs to `user_ids[i]`)."""

    user_ids: np.ndarray
    item_ids: np.ndarray
    user_factors: np.ndarray  # (n_users, k)
    item_factors: np.ndarray  # (n_items, k)

    @property
    def num_factors(self) -> int:
        return int(self.user_factors.shape[1])


def init_factors(n_pad: int, k: int, key, dtype) -> jnp.ndarray:
    """Uniform(0,1)/sqrt(k) init.  FlinkML seeds per-block uniform factors
    [dep]; bit-parity is impossible across runtimes, so parity is defined as
    equal-or-better RMSE at equal iterations (SURVEY.md §7 'hard parts').
    Drawn on the HOST backend where the process has one
    (``mesh.host_device``) — threefry is device-deterministic so the
    values are identical, and a (10M, 64) accelerator-side draw was 2.6 GB
    of HBM transient that the 10M×1M scale envelope could not afford."""
    with jax.default_device(host_device()):
        return jax.random.uniform(key, (n_pad, k), dtype=dtype) / jnp.sqrt(
            jnp.asarray(k, dtype)
        )


def _pad_factors(problem: BlockedProblem, D: int, k: int, dtype,
                 uf_raw: np.ndarray, itf_raw: np.ndarray):
    """Dense-id (n_users, k)/(n_items, k) factors -> block-shaped slot
    layout (D, per_block, k); the strip and bucket-padding slots stay
    zero."""
    uf0 = np.zeros((problem.u.per_block * D, k), dtype=dtype)
    uf0[problem.u.perm] = uf_raw
    itf0 = np.zeros((problem.i.per_block * D, k), dtype=dtype)
    itf0[problem.i.perm] = itf_raw
    # stay NUMPY: jnp.asarray would stage a full unsharded copy on the
    # default device before device_put re-shards it (2x HBM transient)
    return (
        uf0.reshape(D, problem.u.per_block, k),
        itf0.reshape(D, problem.i.per_block, k),
    )


def _set_layout_gauges(problem: BlockedProblem, config: ALSConfig,
                       mesh: Mesh) -> None:
    """What the compiled sweep streams and how it solves, for whoever reads
    the registry, over both sides and all devices: factor-table slots, the
    strips of zero slots among them (``rows``), those of them on the
    per-chunk route (``fused_rows``:
    ``solves_per_chunk`` per side); assembly steps an iteration, one per
    straight-line bucket and one per lax.map chunk (``chunks``); the bytes
    one device's (per_block, k, k) normal equations take or would take, the
    larger side's (``normal_eq_bytes``); the rating slots the gather reads,
    each rating once a side plus the bucket ladder's padding (``entries``),
    and the padding alone (``pad_entries``); the zero slots that padding is
    spread over, one strip a block (``pad_slots``); the segments of the
    table each half gathers from (``table_segments{kind=u|i}``, 1 for a
    table read whole) and the padded entries gathered from a segment, 0
    where no side is cut (``segmented_entries``: a cut side's pieces carry
    their own pads and count in ``entries`` as they are gathered); the rank
    (``rank``) and
    the systems a grid step of the Pallas solver takes (``solver_tile``: the
    smallest over the entries this sweep runs, and one ``{kind=<layout>}``
    child an entry, 0 for an entry it does not run and under the ``lax``
    solver); the bytes of a factor entry as it is exchanged and gathered
    (``exchange_itemsize``: 2 under the bfloat16 exchange) and the padded
    entries the einsum pair contracts (``einsum_entries``: all of
    ``entries`` or, where the assembly kernel serves, 0)."""
    D, k = num_blocks(mesh), config.num_factors
    itemsize = np.dtype(config.dtype).itemsize
    exchange, how = _exchange_and_assembly(config,
                                           mesh.devices.flat[0].platform)
    y_itemsize = exchange.itemsize if exchange else itemsize
    per_chunk = _routes(problem, config, mesh)
    cuts = _cuts(problem, config, mesh)
    rows = fused_rows = chunks = entries = segmented = 0
    for name, side in (("u", problem.u), ("i", problem.i)):
        rows += D * side.per_block
        fused_rows += D * side.per_block * per_chunk[name]
        for bucket in _calls(side, cuts[name], per_chunk[name]):
            for r, w in bucket:
                C = _chunk_rows(r, w, k, y_itemsize, itemsize, how,
                                config.implicit, per_chunk[name])
                chunks += D * (1 if C is None else -(-r // C))
                entries += D * w * r
                segmented += D * w * r * (cuts[name] is not None)
    reg = obs_metrics.get_registry()
    reg.gauge("tpums_als_segmented_entries").set(segmented)
    for name, cut in cuts.items():
        reg.gauge("tpums_als_table_segments", kind=name).set(
            cut.segments if cut else 1)
    reg.gauge("tpums_als_rows").set(rows)
    reg.gauge("tpums_als_fused_rows").set(fused_rows)
    reg.gauge("tpums_als_chunks").set(chunks)
    reg.gauge("tpums_als_normal_eq_bytes").set(
        max(problem.u.per_block, problem.i.per_block) * k * k * itemsize)
    reg.gauge("tpums_als_entries").set(entries)
    reg.gauge("tpums_als_einsum_entries").set(
        0 if how == "kernel" else entries)
    reg.gauge("tpums_als_exchange_itemsize").set(y_itemsize)
    reg.gauge("tpums_als_pad_entries").set(entries - 2 * problem.nnz)
    reg.gauge("tpums_als_pad_slots").set(2 * D * _PAD_STRIP)
    reg.gauge("tpums_als_rank").set(k)
    tiles = _solver_tiles(problem, config, mesh.devices.flat[0].platform,
                          per_chunk, cuts)
    reg.gauge("tpums_als_solver_tile").set(min(tiles.values(), default=0))
    for layout in ("lane_major", "batch_major"):
        reg.gauge("tpums_als_solver_tile", kind=layout).set(
            tiles.get(layout, 0))


def compile_fit(
    problem: BlockedProblem,
    config: ALSConfig,
    mesh: Mesh,
    init: Optional[Tuple[np.ndarray, np.ndarray]] = None,
):
    """-> (fit_fn, dev_args): the compiled blocked-ALS sweep plus its
    device-resident, block-sharded inputs.  ``fit_fn(iterations, *dev_args)``
    returns the factor shards as device arrays.  ``als_fit`` drives this;
    benchmarks call ``fit_fn`` directly so host<->device transfer stays out
    of the timed region.  Phases: ``als.prepare.segment`` (only where a
    side's table is gathered in segments: its lists cut at their
    boundaries, ``_cuts``), ``als.place`` (the host draw of starting
    factors, the slot layout, every ``device_put``) and ``als.sweep`` (the
    jitted sweep looked up or made, the layout gauges); the sweep is traced,
    lowered and compiled by the first ``fit_fn`` call, which the
    ``tpums_jax_*_seconds_total`` counters see."""
    D = num_blocks(mesh)
    k = config.num_factors
    dtype = config.dtype
    # the routing tables are host prep (phase als.prepare.route, where a
    # mesh of more than one device builds them), not placement
    plan = _exchange_plan(problem, D)
    # so are a cut side's pieces (phase als.prepare.segment)
    cuts = _cuts(problem, config, mesh)

    # enqueue only: device_put returns before the transfer ends, and its
    # tail falls to whoever waits first (the first fit_fn call)
    with tracing.phase("als.place"):
        if init is None:
            key_u, key_i = jax.random.split(jax.random.PRNGKey(config.seed))
            # draw in dense-id space (first n rows of the padded draw,
            # keeping the draw shape stable for reproducibility) and place
            # via perm — unowned slots stay zero so the implicit mode's
            # psum'd Gramian (and any future dense reduction over the table)
            # never sees them
            init = (
                np.asarray(init_factors(
                    problem.u.per_block * D, k, key_u, dtype
                ))[: problem.n_users],
                np.asarray(init_factors(
                    problem.i.per_block * D, k, key_i, dtype
                ))[: problem.n_items],
            )
        uf0, itf0 = _pad_factors(problem, D, k, dtype, init[0], init[1])

        shard3 = block_sharding(mesh, rank=3)
        shard2 = block_sharding(mesh, rank=2)
        # single-process: device_put straight from numpy — an intermediate
        # jnp.asarray stages an unsharded default-device copy first,
        # doubling the HBM transient for every array (the 10Mx1M envelope
        # OOM'd on it).  multi-process: device_put of raw numpy onto a
        # multi-host sharding routes through multihost_utils.assert_equal
        # (a cross-host allgather of the full array) and breaks under the
        # DCN test harness — keep the committed-local-array path there.
        def put(a, sharding):
            if jax.process_count() > 1:
                a = jnp.asarray(a)
            return jax.device_put(a, sharding)

        dev_args = [put(uf0, shard3), put(itf0, shard3)]
        for name, side in (("u", problem.u), ("i", problem.i)):
            for a in _flat_side_args(side, dtype, routed=plan[name],
                                     cut=cuts[name]):
                dev_args.append(put(a, shard2 if a.ndim == 2 else shard3))
    with tracing.phase("als.sweep"):
        fit_fn = _cached_sweep(problem, config, mesh)
        _set_layout_gauges(problem, config, mesh)
    return fit_fn, dev_args


def warm_start_factors(
    user_ids: np.ndarray,
    item_ids: np.ndarray,
    prev_user: Dict[int, np.ndarray],
    prev_item: Dict[int, np.ndarray],
    k: int,
    seed: int = 42,
    dtype=np.float32,
) -> Tuple[np.ndarray, np.ndarray]:
    """Align a previously trained model onto a NEW problem's id space ->
    ``(init_user_factors, init_item_factors)`` in dense-id order.

    The continuous-training autopilot retrains on a grown ratings window
    whose entity sets overlap — but rarely equal — the serving model's:
    rows for ids the previous model knows are carried over verbatim (the
    warm start that cuts iterations-to-converge on incremental data),
    ids the model has never seen fall back to the cold seed draw (the
    same ``init_factors`` family a cold fit would use, so a 100%-novel
    window degrades exactly to a cold start, not to zeros — a zero row
    is a stationary point of the user half-sweep for users with only
    novel items).
    """
    user_ids = np.asarray(user_ids)
    item_ids = np.asarray(item_ids)
    key_u, key_i = jax.random.split(jax.random.PRNGKey(seed))
    # np.array (copy): jax buffers come back as read-only views
    uf = np.array(init_factors(len(user_ids), k, key_u, dtype))
    itf = np.array(init_factors(len(item_ids), k, key_i, dtype))
    for ids, table, out in ((user_ids, prev_user, uf),
                            (item_ids, prev_item, itf)):
        for row, id_ in enumerate(ids):
            vec = table.get(int(id_))
            if vec is not None and len(vec) == k:
                out[row] = np.asarray(vec, dtype=dtype)
    return uf, itf


def als_fit(
    users: np.ndarray,
    items: np.ndarray,
    ratings: np.ndarray,
    config: ALSConfig,
    mesh: Mesh,
    problem: Optional[BlockedProblem] = None,
    init: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    temporary_path: Optional[str] = None,
    step_timer=None,
    init_user_factors: Optional[np.ndarray] = None,
    init_item_factors: Optional[np.ndarray] = None,
) -> ALSModel:
    """Train ALS factors for the given rating triples on the mesh.

    `init`, when given, is (user_factors (n_users, k), item_factors
    (n_items, k)) in dense-id order — used by tests to pin the starting
    point so different block counts are exactly comparable.

    `init_user_factors` / `init_item_factors`: the warm-start override
    (must be given together, mutually exclusive with `init`) — the same
    dense-id-order arrays as `init`, named for the retrain path where the
    starting point is the CURRENT SERVING MODEL rather than a test pin
    (``warm_start_factors`` aligns a served model onto the new window's
    id space).  A zero-iteration warm-started fit returns the init
    verbatim (modulo dtype), which is what the parity test pins.

    `temporary_path` (the reference's setTemporaryPath, ALSImpl.scala:42-44):
    run iterations one at a time, materializing the factors to disk at every
    iteration boundary, and resume from the latest matching snapshot if one
    exists.  Without it the whole loop is one fused XLA program.

    `step_timer`: optional ``utils.profiling.StepTimer``; in staged mode each
    iteration (device step + snapshot write) is timed as one step.
    """
    D = num_blocks(mesh)
    if problem is None:
        problem = prepare_blocked(users, items, ratings, D)
    k = config.num_factors
    dtype = config.dtype
    if (init_user_factors is None) != (init_item_factors is None):
        raise ValueError(
            "init_user_factors and init_item_factors must be given together"
        )
    if init_user_factors is not None:
        if init is not None:
            raise ValueError(
                "init and init_user_factors/init_item_factors are mutually "
                "exclusive"
            )
        uf_w = np.asarray(init_user_factors, dtype=dtype)
        itf_w = np.asarray(init_item_factors, dtype=dtype)
        if uf_w.shape != (problem.n_users, k) or \
                itf_w.shape != (problem.n_items, k):
            raise ValueError(
                f"warm-start shapes {uf_w.shape}/{itf_w.shape} do not match "
                f"problem ({problem.n_users}, {k})/({problem.n_items}, {k})"
            )
        init = (uf_w, itf_w)
    shard3 = block_sharding(mesh, rank=3)
    fit_fn, dev_args = compile_fit(problem, config, mesh, init=init)
    n_users_pad = problem.u.per_block * D
    n_items_pad = problem.i.per_block * D

    def to_dense(uf_d, itf_d):
        # multi-process runs: factor shards live on remote hosts too, so
        # materialization is a cross-host allgather (plain copy locally)
        from ..parallel.distributed import to_host_array

        u = to_host_array(uf_d).reshape(n_users_pad, k)[problem.u.perm]
        i = to_host_array(itf_d).reshape(n_items_pad, k)[problem.i.perm]
        return u, i

    if temporary_path is None:
        uf, itf = fit_fn(jnp.asarray(config.iterations, jnp.int32), *dev_args)
        uf, itf = to_dense(uf, itf)
    else:
        from ..parallel.distributed import is_primary

        meta = _staging_meta(problem, config, init,
                             mesh.devices.flat[0].platform)
        multi = jax.process_count() > 1
        # multi-process: exactly one writer, and process 0's snapshot is
        # authoritative for the resume point — local scans could disagree
        # (per-host disks, partially replicated shared storage) and a
        # divergent `start` would desynchronize the collective steps below
        snap = (
            load_staged(temporary_path, meta, max_iteration=config.iterations)
            if (not multi or is_primary())
            else None
        )
        start = 0 if snap is None else snap[0]
        if multi:
            from jax.experimental import multihost_utils

            start = int(
                multihost_utils.broadcast_one_to_all(
                    np.asarray(start, np.int32)
                )
            )
            if start > 0:
                uf_raw = (
                    snap[1] if snap is not None
                    else np.zeros((problem.n_users, k), dtype)
                )
                itf_raw = (
                    snap[2] if snap is not None
                    else np.zeros((problem.n_items, k), dtype)
                )
                uf_raw = multihost_utils.broadcast_one_to_all(
                    uf_raw.astype(dtype)
                )
                itf_raw = multihost_utils.broadcast_one_to_all(
                    itf_raw.astype(dtype)
                )
                snap = (start, np.asarray(uf_raw), np.asarray(itf_raw))
        if start > 0:
            # operational marker — harnesses (and operators) distinguish a
            # genuine resume from a cold rerun by this line, since snapshot
            # pruning makes the staging dir's final contents identical
            print(f"[ALS] staging: resuming from iteration {start} "
                  f"({temporary_path})", flush=True)
            _, uf_raw, itf_raw = snap
            uf_s, itf_s = _pad_factors(problem, D, k, dtype, uf_raw, itf_raw)
            dev_args[0] = jax.device_put(uf_s, shard3)
            dev_args[1] = jax.device_put(itf_s, shard3)
        one = jnp.asarray(1, jnp.int32)
        uf_d, itf_d = dev_args[0], dev_args[1]
        # the loop carries its own factor buffers from here on — drop the
        # list's references so the initial copies don't pin HBM all run long
        dev_args[0] = dev_args[1] = None
        timer = step_timer if step_timer is not None else contextlib.nullcontext()
        for it in range(start, config.iterations):
            with timer:
                uf_d, itf_d = fit_fn(one, uf_d, itf_d, *dev_args[2:])
                uf, itf = to_dense(uf_d, itf_d)
                if not multi or is_primary():
                    save_staged(temporary_path, it + 1, uf, itf, meta)
        if start == config.iterations:  # fully-resumed: nothing left to run
            uf, itf = to_dense(uf_d, itf_d)
    return ALSModel(
        user_ids=problem.user_ids,
        item_ids=problem.item_ids,
        user_factors=uf,
        item_factors=itf,
    )


# ---------------------------------------------------------------------------
# prediction / evaluation ops
# ---------------------------------------------------------------------------

@partial(jax.jit, donate_argnums=())
def _predict_dense(uf, itf, u_idx, i_idx):
    return jnp.sum(jnp.take(uf, u_idx, axis=0) * jnp.take(itf, i_idx, axis=0), axis=-1)


def _predict_chunk_rows() -> int:
    # bound the two (chunk, k) gather transients: an unchunked 20M-pair
    # predict at k=50 compiled to a 19 GB program and OOM'd 16 GB HBM
    # (2026-07-31, earlier installation); 4M rows keeps transients ~2-3 GB
    return int(os.environ.get("FLINK_MS_PREDICT_CHUNK", 4_000_000))


def predict(model: ALSModel, users: np.ndarray, items: np.ndarray) -> np.ndarray:
    """Batched scores for raw (user, item) id pairs; unknown ids score 0
    (callers substitute the MEAN cold-start vector — SGD.java:219-234).
    Large batches run in fixed-size device chunks (one executable, padded
    tail) so evaluation over a full ratings file never exceeds HBM."""
    u_idx = np.searchsorted(model.user_ids, users)
    u_idx_c = np.clip(u_idx, 0, len(model.user_ids) - 1)
    u_ok = model.user_ids[u_idx_c] == users
    i_idx = np.searchsorted(model.item_ids, items)
    i_idx_c = np.clip(i_idx, 0, len(model.item_ids) - 1)
    i_ok = model.item_ids[i_idx_c] == items
    n = len(u_idx_c)
    C = _predict_chunk_rows()
    uf_d = jnp.asarray(model.user_factors)
    itf_d = jnp.asarray(model.item_factors)
    if n <= C:
        preds = np.asarray(
            _predict_dense(uf_d, itf_d, jnp.asarray(u_idx_c),
                           jnp.asarray(i_idx_c))
        )
    else:
        preds = np.empty(n, model.user_factors.dtype)
        for s in range(0, n, C):
            e = min(s + C, n)
            uc, ic = u_idx_c[s:e], i_idx_c[s:e]
            if e - s < C:  # pad the tail: same shapes -> same executable
                pad = C - (e - s)
                uc = np.pad(uc, (0, pad))
                ic = np.pad(ic, (0, pad))
            preds[s:e] = np.asarray(
                _predict_dense(uf_d, itf_d, jnp.asarray(uc),
                               jnp.asarray(ic))
            )[: e - s]
    return np.where(u_ok & i_ok, preds, 0.0)


def rmse(model: ALSModel, users, items, ratings) -> float:
    p = predict(model, np.asarray(users), np.asarray(items))
    err = np.asarray(ratings, dtype=np.float64) - p
    return float(np.sqrt(np.mean(err * err)))
