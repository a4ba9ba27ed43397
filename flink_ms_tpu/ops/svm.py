"""CoCoA distributed linear SVM on a TPU device mesh.

TPU-native re-design of the capability behind ``SVM().fit(trainingDS)``
(reference call site ``flink-svm/.../SVMImpl.scala:24-29``; solver semantics
are FlinkML's CoCoA + local SDCA [dep], SURVEY.md §2.2):

    min_w  (λ/2)||w||² + (1/n) Σ_j max(0, 1 − y_j w·x_j)

Data is split into ``Blocks`` = K *logical* blocks (``setBlocks``,
SVMImpl.scala:25) laid out as K independent SDCA chains over a D-device
mesh — K may exceed D, in which case C = ceil(K/D) chains are stacked per
device and run under ``vmap``: every ``fori_loop`` step advances C chains
at once (a (C, L) gather/scatter instead of one row), so the serial depth
per round is rows-per-chain, not rows-per-device.  That is the TPU answer
to the reference's one-chain-per-TaskManager layout: more blocks = shorter
chains = more hardware parallelism, with the classic CoCoA convergence
story governing the block count.

Each chain runs H local SDCA steps (closed-form hinge dual update of
Shalev-Shwartz & Zhang) against a chain-local copy of the weight vector;
chains exchange through a single ``psum`` over ICI per outer round.  Two
combination modes:

- ``mode="avg"`` (default; FlinkML/CoCoA-v1 parity, Jaggi et al. 2014):
  block deltas are *averaged*, w += (β/K)·ΣΔw_k with β = stepsize, and the
  local subproblem is unscaled (σ′ = 1).
- ``mode="add"`` (CoCoA+, Ma, Smith, Jaggi et al. 2015 "Adding vs.
  Averaging in Distributed Primal-Dual Optimization"): block deltas are
  *added*, w += γ·ΣΔw_k with γ = stepsize, and each local subproblem is
  smoothed by σ′ = γ·K (the safe choice) — both the dual step denominator
  and the chain-local w view carry σ′.  At large K (the TPU-friendly
  regime) "add" keeps full per-round progress where averaging dilutes it
  by 1/K.

The whole fit is one XLA program with a *dynamic* outer-round count
(``fori_loop`` with a traced bound), so one compiled executable serves any
``--iteration`` value — benchmarks time extra rounds without recompiling.

Examples are held in one of two row layouts, picked from the data alone by
``stores_rows_dense`` (no knob): **sparse** (feature ids and values, 8 B a
stored entry in f32) below a density of 50%, **dense** (one value a
feature, no ids) from there up, whichever stores fewer bytes.

Two engines run the local steps (``SVMConfig.inner``).  The scatter engine
indexes one row per chain per step.  The Gram engine touches the weight
vector twice a round, once for the round-start margins and once for
Δw = XᵀΔα.  On sparse rows those are one gather and one scatter-add, whose
time is their stored entries times 7 ns whatever an entry holds, so both
loop over a copy of the rows cut into tiles of 8 positions x 1,024 rows,
the rows ordered by length (``_tail_tiles``: a block of rows is stored as
deep as its own longest row).  Where the device reports its memory, the
hottest columns leave that price: ``head_width`` asks the dense layout's
question a column at a time and by time, and the columns that are cheaper
streamed, zeros and all, than gathered are held as one dense block, the
head, beside the tiles of the others, the tail.  Measured on one TPU v5e in
``rcv1-cocoa.cocoa-rounds`` (PERF.md §5, PR 41; 8192 chains x 83 rows,
1,536 of 47,236 columns, 54.8% of the 49.6M entries, a quarter of the
chip): a round takes 0.392 s, the gather and the scatter-add 0.19 each
over 24.9M stored entries, the block's two passes 5.5 ms each.  Where no
column is worth a head (a CPU, which reports no memory; bf16 state) the
tail is the whole row and the same round runs without the block's operands.
On dense rows they are two products over X, ``X w`` and
``Xᵀ Δα``, each one pass over the matrix where it lies: measured in
``epsilon-cocoa-plus.dense-rounds`` (PERF.md §5, PR 37; CoCoA+, 8192
chains x 49 rows, 400,000 x 2,000, every cell stored, 3.2 GB): a round
takes 0.0313 s, each pass 0.00425 (92% of the chip's 819 GB/s), and the 49
steps between them 0.0222, 71% of the round.  The same data through the
sparse layout (PR 37's probe of the parent): 13.2 s a round, 6.5 GB of ids
and values on the device, 46 GB on the host.

Those steps were XLA's: six per-element gathers and scatters a step.  On a
TPU the Gram engine now runs all of a round's steps in one Pallas kernel
(``sdca_pallas.sdca_steps_lanes``, chosen by ``resolve_step``), the chains
on the lanes and each block's Gram rows resident in VMEM: 0.0004 s of a
0.0090 s round at epsilon's shape, 0.0014 of 0.767 at RCV1's (PERF.md §5,
PR 38).  Every CPU fit, the scatter engine and bf16 state keep the XLA step.

Surfaced knobs follow FlinkML's parameter set: Blocks, Iterations,
LocalIterations, Regularization, Stepsize, Seed [dep]; ThresholdValue /
OutputDecisionFunction live client-side (SVMPredict.java:33-34,80-86).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.formats import SparseData
from ..obs import metrics as obs_metrics
from ..obs import tracing
from ..parallel.mesh import (BLOCK_AXIS, block_sharding, device_memory,
                             num_blocks)


@dataclasses.dataclass(frozen=True)
class SVMConfig:
    iterations: int = 10          # outer CoCoA rounds (SVMImpl --iteration)
    local_iterations: int = 10    # SDCA steps per block per round [dep default]
    regularization: float = 1.0   # λ [dep default]
    stepsize: float = 1.0         # β (avg) / γ (add) scaling of the update
    seed: int = 0
    mode: str = "avg"             # "avg" = CoCoA-v1 parity, "add" = CoCoA+
    # local-subproblem smoothing σ' for mode="add" (CoCoA+).  None = the
    # provably safe γ·K.  Values in [1, γK) are the aggressive regime:
    # valid when blocks' updates rarely collide (sparse data, e.g. RCV1);
    # the fit stays convergent in practice and each round makes up to
    # γK/σ' times more progress.  Ignored in avg mode.
    sigma_prime: Optional[float] = None
    dtype: jnp.dtype = jnp.float32
    # Inner-loop engine.  "scatter": every SDCA step gathers/scatters a
    # chain-local copy of the (d,)-dim weight vector — O(L) work per step
    # but random access into (C, d) state.  "gram": precompute each chain's
    # (H, H) row-Gram matrix once (densify-matmul on the MXU), keep a
    # running margin vector wx[i] = w_loc·x_i, and make every step a dense
    # (C, H) AXPY — the weight vector is touched once per ROUND (one
    # gather for wx0, one scatter for X^T dalpha) instead of once per
    # step.  Same update sequence (same RNG, same closed-form dual step),
    # reassociated arithmetic.  "auto": gram when the (C, H, H) tensor
    # fits FLINK_MS_SVM_GRAM_BYTES (default 1 GiB per device).  Both
    # engines run on both row layouts: on dense rows the scatter step
    # reads one dense row a chain and the Gram engine's two touches of w
    # are products over X.
    inner: str = "auto"

    def __post_init__(self):
        if self.mode not in ("avg", "add"):
            raise ValueError("mode must be avg or add")
        if self.sigma_prime is not None and self.sigma_prime < 1.0:
            raise ValueError("sigma_prime must be >= 1")
        if self.inner not in ("auto", "gram", "scatter"):
            raise ValueError("inner must be auto|gram|scatter")


@dataclasses.dataclass
class SVMModel:
    weights: np.ndarray  # (n_features,) dense primal vector

    def decision_function(self, data: SparseData) -> np.ndarray:
        if data.n_examples == 0:
            return np.zeros(0)
        contrib = data.values * self.weights[data.indices]
        # reduceat over CSR row starts; empty rows need explicit zeroing
        # (reduceat on an empty segment returns the next element)
        sums = np.zeros(data.n_examples)
        starts = data.indptr[:-1]
        nonempty = data.indptr[1:] > starts
        if contrib.size:
            red = np.add.reduceat(contrib, np.minimum(starts, contrib.size - 1))
            sums[nonempty] = red[nonempty]
        return sums

    def hinge_loss(self, data: SparseData, lambda_: float) -> float:
        margins = data.labels * self.decision_function(data)
        return float(
            np.mean(np.maximum(0.0, 1.0 - margins))
            + 0.5 * lambda_ * float(self.weights @ self.weights)
        )


# ---------------------------------------------------------------------------
# host-side layout
# ---------------------------------------------------------------------------

# The dense layout's products over X (round-start margins, Δw, the Gram
# build).  A default-precision f32 product on a TPU is one bf16 pass.
_DENSE_PRECISION = "highest"


def stores_rows_dense(n_examples: int, n_features: int, nnz: int,
                      itemsize: int) -> bool:
    """The row layout, from the data alone: dense where the whole
    ``(examples, features)`` value matrix takes no more bytes than the
    stored entries' ids and values do (4 B an id beside each value), that
    is from a density of 1 / (1 + 4 / itemsize) up: 50% in float32.  Below
    it an entry costs its id and a gather; above it the ids say nothing a
    position does not, and the round streams X instead of indexing w.  The
    question is asked once for the whole matrix and by bytes stored;
    ``head_width`` asks it of a sparse layout's columns one at a time and by
    time, so RCV1 (0.16%) is sparse here and still streams its 1,536
    hottest columns."""
    return nnz > 0 and n_examples * n_features * itemsize <= nnz * (4 + itemsize)


# What the column split of the sparse layout is decided by.  A gathered or
# scatter-added entry of the Gram engine's round costs _ENTRY_NS on a TPU
# v5e whatever the layout (6.85 ns a stored entry in cocoa_margins_s and
# in cocoa_dw_s of rcv1-cocoa.cocoa-rounds: PERF.md section 5, PR 38;
# 6.8-8.75 in PR 32's probes of the two operations alone), and a pass over a
# dense f32 matrix streams _STREAM_BYTES_PER_NS (0.75 TB/s, 91.5-91.9% of
# the chip's 819 GB/s: cocoa_dense_margins_roofline and _dw_roofline of
# epsilon-cocoa-plus.dense-rounds, PERF.md section 5, PR 38).  The dense
# block may take _HEAD_SHARE of a device's memory: beside it the round holds
# the tail, the Gram tensor and w, and the build holds the padded
# rectangles for a moment (ops/als._MATERIALISE_SHARE is the precedent).
_ENTRY_NS = 6.85
_STREAM_BYTES_PER_NS = 750.0
_HEAD_SHARE = 0.25
_LANES = 128


def head_width(col_counts, slots: int, itemsize: int,
               memory_bytes: Optional[int]) -> int:
    """How many of a sparse layout's hottest feature columns a device holds
    as one dense ``(slots, F)`` value block and streams, the entries of the
    other columns (the tail) staying gathered: a pure function of what a
    fit can observe, no knob.  ``col_counts``: the rows of a device that
    hold each column; ``slots``: its row slots; ``memory_bytes``: its memory
    as the runtime reports it.  Columns are taken hottest first while
    streaming one, zeros and all, is cheaper than gathering its entries
    (count x ``_ENTRY_NS`` > slots x itemsize / ``_STREAM_BYTES_PER_NS``:
    from 0.08% of the rows in f32), in whole lane tiles of 128, until the
    block reaches ``_HEAD_SHARE`` of the memory.  A runtime that reports no
    memory (the CPU, where a gather is cheap) takes no head.  At RCV1's
    shape on one TPU v5e (679,936 slots, 16.9 GB): 1,536 columns, 4.18 GB,
    54.8% of the entries; ``rcv1-cocoa.cocoa-rounds`` read 0.39202 s a round
    against the parent's 0.76719 (PERF.md section 6, PR 41)."""
    if not memory_bytes or not slots:
        return 0
    column_bytes = slots * itemsize
    break_even = column_bytes / _STREAM_BYTES_PER_NS / _ENTRY_NS
    cheaper_streamed = int(np.count_nonzero(np.asarray(col_counts) > break_even))
    fits = int(_HEAD_SHARE * memory_bytes // column_bytes)
    return min(cheaper_streamed, fits) // _LANES * _LANES


@dataclasses.dataclass
class BlockedSVMProblem:
    """Examples split into K logical blocks (K = the reference's
    ``setBlocks``; independent of the device count — the kernel stacks
    ceil(K/D) blocks per device), their rows held in one of two layouts
    that ``prepare_svm_blocked`` picks by ``stores_rows_dense``: per-row
    padded sparse storage (``idx`` and ``val``, every row padded to the
    longest), or one dense value row per example (``idx`` is None, ``val``
    spans every feature, absent features are zeros).

    Padding rows have label 0 and empty features; the SDCA step masks them
    (zero row norm => zero update), so they never affect the solution.

    Who reads what.  Sparse: the scatter engine indexes ``idx`` / ``val``
    by row inside every step, so the padded rectangles are its device
    operands; the Gram engine reads them once, chain by chain, to build its
    Gram tensor, and runs its rounds over the tiles that ``compile_svm_fit``
    cuts out of them by ``row_len`` (``_tail_tiles``).  Dense: ``val`` goes
    to the device once, as
    ``(K·rows, features)``, and both engines, the Gram build and the
    round's two products read it there.  Both read ``label`` and
    ``sq_norm``.

    What the choice is worth at epsilon's shape (400,000 x 2,000, every
    cell stored; one TPU v5e, PERF.md §5-6, PR 37): dense, 3.2 GB on the
    host and on the device, a CoCoA+ round of 0.0313 s whose two passes
    over X take 4.25 ms each; the same rows sparse, 46 GB of host memory,
    6.5 GB of ids and values on the device and 13.2 s a round.  At RCV1's
    0.16% the rule keeps the sparse layout, and on a device that reports
    its memory ``compile_svm_fit`` splits it by column (``head_width``): a
    sparse row's entries are stored coldest column first (``col_rank``), so
    that whatever number of hottest columns the fit holds dense, the other
    columns' entries, which the round still gathers, are the row's first.
    """

    n_blocks: int
    n_examples: int      # real examples (pre-padding)
    n_features: int
    rows_per_block: int
    idx: Optional[np.ndarray]  # (K, rows_pb, L) int32 feature indices
    #                      (0-based); None on the dense layout
    val: np.ndarray      # (K, rows_pb, L) values, 0 where padded; dense:
    #                      (K, rows_pb, n_features)
    label: np.ndarray    # (K, rows_pb) +-1, 0 for padding rows
    sq_norm: np.ndarray  # (K, rows_pb) ||x_j||^2
    row_len: np.ndarray  # (K, rows_pb) int32 entries the example came
    #                      with (sparse: stored in the row's first
    #                      positions), 0 for padding rows
    col_count: Optional[np.ndarray] = None  # (n_features,) rows that hold
    #                      each feature; None on the dense layout
    col_rank: Optional[np.ndarray] = None   # (n_features,) a feature's
    #                      place by descending col_count, 0 the hottest; a
    #                      sparse row's entries descend by it

    @property
    def dense(self) -> bool:
        return self.idx is None


# The host passes over a layout's rows run in strips of 16 MB of float64
# (temporaries of this size are reused from the heap; larger ones are mapped
# and faulted in anew at every strip, 2.5x slower at 64 MB) on a few threads
# (epsilon's 3.2 GB matrix and its norms: 7.1-7.8 s on 8 threads, 23.6-27.8
# on one; PERF.md section 6, PR 37).
_STRIP_BYTES = 16 << 20
_STRIP_THREADS = 8


def _each_strip(fn, n_rows: int, width: int) -> None:
    """``fn(lo, hi)`` over strips of rows, on ``_STRIP_THREADS`` threads:
    numpy lets go of the interpreter lock inside its copies and casts, and
    a fresh matrix is faulted in faster by several threads than by one."""
    step = max(_STRIP_BYTES // (max(width, 1) * 8), 1)
    bounds = [(lo, min(lo + step, n_rows)) for lo in range(0, n_rows, step)]
    with ThreadPoolExecutor(_STRIP_THREADS) as pool:
        # list(): a strip's exception is raised here, not dropped
        list(pool.map(lambda b: fn(*b), bounds))


def _strip_entries(data: SparseData, lens, ex):
    """The CSR entries of examples ``ex``, row after row -> (owner, pos,
    flat): the row of ``ex`` each entry belongs to, its position inside
    that row, and its place in ``data.indices`` / ``data.values``."""
    mine = lens[ex]
    owner = np.repeat(np.arange(len(ex)), mine)
    pos = np.arange(int(mine.sum())) - (np.cumsum(mine) - mine)[owner]
    return owner, pos, data.indptr[ex][owner] + pos


def _padded_rows(data: SparseData, lens, order, slots: int, dtype,
                 col_rank):
    """-> (idx, val), each (slots, L): slot s holds example order[s]'s
    entries, padded to the longest row, **coldest column first**
    (descending ``col_rank``): whatever number F of hottest columns a fit
    then holds dense (``head_width``), the entries of the other columns,
    its tail, are the row's first ones.  Nothing else reads a row's order:
    the Gram build scatters by id and a margin is a sum.  A strip of rows
    at a time, on threads, as the dense layout is filled."""
    n, d = data.n_examples, data.n_features
    L = max(int(lens.max()) if n else 1, 1)
    idx = np.zeros((slots, L), dtype=np.int32)
    val = np.zeros((slots, L), dtype=dtype)

    def fill(lo, hi):
        owner, pos, flat = _strip_entries(data, lens, order[lo:hi])
        ids = data.indices[flat]
        by = np.argsort(owner * d + (d - 1 - col_rank[ids]), kind="stable")
        idx[lo:hi][owner, pos] = ids[by]
        val[lo:hi][owner, pos] = data.values[flat[by]]

    _each_strip(fill, n, L)
    return idx, val


def _dense_rows(data: SparseData, lens, order, slots: int, dtype):
    """-> val (slots, features): slot s holds example order[s]'s values
    at their features' columns, zeros elsewhere.  No id rectangle is
    built: the host holds the caller's data and this one matrix, filled a
    strip of rows at a time.  Where every row is full (a dense LIBSVM
    file) a strip in feature order is one copy; any other strip is
    scattered by id (a row's ids are distinct)."""
    n, d = data.n_examples, data.n_features
    val = np.zeros((slots, d), dtype=dtype)
    full = n > 0 and int(lens.min()) == d
    if full:
        ids2, vals2 = data.indices.reshape(n, d), data.values.reshape(n, d)

    def fill(lo, hi):
        ex = order[lo:hi]
        if full:
            ids, vals = ids2[ex], vals2[ex]
            if (ids == np.arange(d)).all():
                val[lo:hi] = vals
                return
            owner = np.arange(hi - lo)[:, None]
        else:
            owner, _, flat = _strip_entries(data, lens, ex)
            ids, vals = data.indices[flat], data.values[flat]
        val[lo:hi][owner, ids] = vals

    _each_strip(fill, n, d)
    return val


def prepare_svm_blocked(
    data: SparseData, n_blocks: int, seed: int = 0, dtype=np.float32
) -> BlockedSVMProblem:
    """Vectorized re-layout: shuffle examples across K blocks and store
    their rows in the layout ``stores_rows_dense`` picks for the data:
    every row padded to the max nnz, or every row dense (static shapes for
    XLA either way).  Phase ``svm.prepare``."""
    with tracing.phase("svm.prepare"):
        n = data.n_examples
        rows_pb = -(-n // n_blocks) if n else 1
        slots = n_blocks * rows_pb
        lens = (data.indptr[1:] - data.indptr[:-1]).astype(np.int64)
        # slot s <- example order[s]
        order = np.random.default_rng(seed).permutation(n)
        col_count = col_rank = None
        if stores_rows_dense(n, data.n_features, int(lens.sum()),
                             np.dtype(dtype).itemsize):
            idx, val = None, _dense_rows(data, lens, order, slots, dtype)
        else:
            # a row's ids are distinct: an id's occurrences are its rows
            col_count = np.bincount(data.indices, minlength=data.n_features)
            col_rank = np.empty(data.n_features, np.int64)
            col_rank[np.argsort(-col_count, kind="stable")] = np.arange(
                data.n_features)
            idx, val = _padded_rows(data, lens, order, slots, dtype, col_rank)
        label = np.zeros((slots,), dtype=dtype)
        row_len = np.zeros((slots,), dtype=np.int32)
        row_len[:n] = lens[order]
        signs = np.sign(data.labels[order]).astype(dtype)
        label[:n] = np.where(signs == 0, 1.0, signs)  # labels must be +-1
        sq_norm = np.empty((slots,), dtype=dtype)

        def norms(lo, hi):
            sq_norm[lo:hi] = np.sum(
                val[lo:hi].astype(np.float64) ** 2, axis=-1)

        _each_strip(norms, slots, val.shape[1])
        # slot s -> (block s // rows_pb, row s % rows_pb): contiguous rows per
        # block, matching the reference's partition-then-iterate layout
        shape = (n_blocks, rows_pb)
        return BlockedSVMProblem(
            n_blocks=n_blocks,
            n_examples=n,
            n_features=data.n_features,
            rows_per_block=rows_pb,
            idx=None if idx is None else idx.reshape(*shape, -1),
            val=val.reshape(*shape, -1),
            label=label.reshape(shape),
            sq_norm=sq_norm.reshape(shape),
            row_len=row_len.reshape(shape),
            col_count=col_count,
            col_rank=col_rank,
        )


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_blocks(a: np.ndarray, Kp: int) -> np.ndarray:
    """Empty blocks appended so that the K blocks shard evenly: an empty
    chain makes zero deltas, and the combination scales by the real K."""
    if Kp == a.shape[0]:
        return a
    return np.pad(a, [(0, Kp - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


# The Gram engine's rounds gather a sparse row's tail: its first
# ``tail_len`` entries, which is the whole row where no column is held
# dense, and with a head (``head_width`` > 0) the entries outside the
# hottest columns, which the round streams.  The tail is stored as tiles of
# _TILE_STEP positions x _TILE_ROWS rows (whole (8, 128) device tiles,
# entry-major: a row's entries down a column, the rows along the lanes), the
# rows ordered by tail length so that a block of rows needs the steps of its
# first row and no more, and the round loops over the tiles that hold an
# entry: their number is an operand, not a shape.  The shapes are a
# function of the TOTAL row lengths alone (a row's tail is no longer than
# the row), so every seed of one length sequence runs one compiled program,
# whichever features it drew.
_TILE_STEP = 8
_TILE_ROWS = 1024
# the head's entries reach the device as pieces of at most this many (row,
# column, value) triples, each scattered into a window of this many rows of
# the zeroed block: one program whatever their number.  A window, because
# the compiler scatters into a flat copy of its operand (PERF.md section 6,
# PR 41: 4.2 GB of scratch held for good when the operand was the block)
_HEAD_CHUNK = 1 << 22
_HEAD_WINDOW = 1 << 16


def _tail_lengths(idx: np.ndarray, row_len: np.ndarray, col_rank, head: int):
    """(slots,) entries of each row outside the ``head`` hottest columns:
    a row's entries descend by ``col_rank`` (``_padded_rows``), so the
    tail ends at the first entry ranked under ``head``, found by bisection."""
    rows, L = np.arange(len(row_len)), idx.shape[1]
    lo, hi = np.zeros(len(row_len), np.int64), row_len.astype(np.int64)
    for _ in range(L.bit_length()):
        mid = (lo + hi) // 2
        in_tail = col_rank[idx[rows, np.minimum(mid, L - 1)]] >= head
        undecided = lo < hi
        lo = np.where(undecided & in_tail, mid + 1, lo)
        hi = np.where(undecided & ~in_tail, mid, hi)
    return lo


def _tile_steps(lens: np.ndarray) -> np.ndarray:
    """(blocks,) the steps of _TILE_STEP positions that each block of
    _TILE_ROWS rows needs, the rows taken longest first."""
    longest = np.sort(lens)[::-1][::_TILE_ROWS]
    return -(-longest // _TILE_STEP)


def _tail_tiles(idx: np.ndarray, val: np.ndarray, row_len: np.ndarray,
                tail_len: np.ndarray, D: int):
    """The rows' tail entries cut out of the padded rectangles into tiles
    -> (ids, val, slot, row0, n_tiles): ``ids`` / ``val`` (D, tiles,
    _TILE_STEP, _TILE_ROWS), a device's tiles block after block, step after
    step, zeros past a row's tail and past ``n_tiles`` (D, 1), the tiles that
    hold an entry; ``slot`` (D, blocks x _TILE_ROWS) the device-local flat
    slot of every tile row (0 for the pads of the last block, which add
    exact zeros) and ``row0`` (D, tiles) the first of them under each tile.
    The tile capacity is what the total lengths would need."""
    S = row_len.size // D
    L = idx.shape[-1]
    idx, val = idx.reshape(D, S, L), val.reshape(D, S, L)
    row_len, tail_len = row_len.reshape(D, S), tail_len.reshape(D, S)
    blocks = -(-S // _TILE_ROWS)
    capacity = max(int(max(_tile_steps(lens).sum() for lens in row_len)), 1)
    ids_t = np.zeros((D, capacity, _TILE_STEP, _TILE_ROWS), np.int32)
    val_t = np.zeros(ids_t.shape, val.dtype)
    slot = np.zeros((D, blocks * _TILE_ROWS), np.int32)
    row0 = np.zeros((D, capacity), np.int32)
    n_tiles = np.zeros((D, 1), np.int32)
    jobs = []
    for dev in range(D):
        order = np.argsort(-tail_len[dev], kind="stable")
        slot[dev, :S] = order
        steps = _tile_steps(tail_len[dev])
        ends = np.cumsum(steps)
        n_tiles[dev] = ends[-1]
        row0[dev, :ends[-1]] = np.repeat(
            np.arange(blocks) * _TILE_ROWS, steps)
        jobs += [(dev, order[b * _TILE_ROWS:(b + 1) * _TILE_ROWS],
                  int(end - n), int(end))
                 for b, (n, end) in enumerate(zip(steps, ends)) if n]

    def fill(job):
        dev, rows, first, last = job
        width = min((last - first) * _TILE_STEP, L)
        keep = np.arange(width) < tail_len[dev, rows, None]
        for src, out in ((idx, ids_t), (val, val_t)):
            block = np.zeros(((last - first) * _TILE_STEP, len(rows)),
                             out.dtype)
            block[:width] = np.where(keep, src[dev, rows, :width], 0).T
            out[dev, first:last, :, :len(rows)] = block.reshape(
                last - first, _TILE_STEP, len(rows))

    with ThreadPoolExecutor(_STRIP_THREADS) as pool:
        list(pool.map(fill, jobs))
    return ids_t, val_t, slot, row0, n_tiles


def _head_pieces(ends: np.ndarray, chunk: int, window: int):
    """A device's head entries, listed row after row (``ends[r]`` of them
    up to and with row r), cut into pieces of at most ``chunk`` entries
    whose rows lie within ``window`` rows -> [(first row of the window,
    first entry, end entry)].  How many pieces, and where they are cut,
    follows the data; their shape does not."""
    pieces, at = [], 0
    while at < ends[-1]:
        row = int(np.searchsorted(ends, at, side="right"))
        first = min(row, len(ends) - window)
        end = min(at + chunk, int(ends[first + window - 1]))
        pieces.append((first, at, end))
        at = end
    return pieces


def _head_entries(idx: np.ndarray, val: np.ndarray, row_len: np.ndarray,
                  tail_len: np.ndarray, col_rank, D: int, window: int):
    """The entries of the head columns, every entry past its row's tail, as
    pieces of a compact list -> (first, rows, cols, vals): ``first`` (D,
    pieces, 1) the device-local row slot at which a piece's window of
    ``window`` rows starts, and per entry its row within that window, its
    column of the block (the feature's ``col_rank``) and its value, (D,
    pieces, chunk) each.  What a piece does not fill names distinct cells
    of the window's first column with value 0.  The chunk is
    ``_HEAD_CHUNK`` or, on small data, what a device's entries of all
    columns would fill: a function of the total lengths."""
    S = row_len.size // D
    L = idx.shape[-1]
    row_len, tail_len = row_len.reshape(D, S), tail_len.reshape(D, S)
    chunk = min(_HEAD_CHUNK,
                _round_up(max(int(row_len.sum(axis=1).max()), 1), _LANES))
    counts = row_len - tail_len
    ends = np.cumsum(counts, axis=1)
    rows, cols = (np.zeros((D, int(ends[:, -1].max())), np.int32)
                  for _ in range(2))
    vals = np.zeros(rows.shape, val.dtype)
    idx, val = idx.reshape(D, S, L), val.reshape(D, S, L)
    pos = np.arange(L)

    def fill(dev, lo, hi):
        mine = ((pos >= tail_len[dev, lo:hi, None])
                & (pos < row_len[dev, lo:hi, None]))
        at = slice(int(ends[dev, lo] - counts[dev, lo]), int(ends[dev, hi - 1]))
        rows[dev, at] = np.repeat(np.arange(lo, hi), counts[dev, lo:hi])
        cols[dev, at] = col_rank[idx[dev, lo:hi][mine]]
        vals[dev, at] = val[dev, lo:hi][mine]

    for dev in range(D):
        _each_strip(functools.partial(fill, dev), S, L)
    cuts = [_head_pieces(ends[dev], chunk, window) for dev in range(D)]
    shape = (D, max(max(len(c) for c in cuts), 1), chunk)
    first = np.zeros(shape[:2] + (1,), np.int32)
    out_rows = np.broadcast_to(
        np.arange(chunk, dtype=np.int32) % window, shape).copy()
    out_cols, out_vals = np.zeros(shape, np.int32), np.zeros(shape, val.dtype)
    for dev, pieces in enumerate(cuts):
        for k, (start, lo, hi) in enumerate(pieces):
            first[dev, k] = start
            out_rows[dev, k, :hi - lo] = rows[dev, lo:hi] - start
            out_cols[dev, k, :hi - lo] = cols[dev, lo:hi]
            out_vals[dev, k, :hi - lo] = vals[dev, lo:hi]
    return first, out_rows, out_cols, out_vals


def resolve_step(platform: Optional[str], inner: str, dtype, h_rows: int,
                 steps: int) -> str:
    """How a compiled fit runs its SDCA steps: "dynamic", XLA's
    ``fori_loop`` over ``vmap(chain_sdca_gram)``, or "kernel", all of a
    round's steps in one Pallas call with the draws hoisted
    (``sdca_pallas.sdca_steps_lanes``).  The kernel engages where a chip run
    priced it: a TPU, the Gram engine, f32 state, and a lane block of 128
    chains that fits the kernel's VMEM twice (``sdca_pallas.fits_vmem``,
    from the rows a chain and the steps a round: up to 113 rows at one local
    pass).  Everything else keeps the XLA step: every CPU fit, the scatter
    engine, bf16 state, a chain too long for VMEM.  One code path for both
    benchmark cells, its block following the rows a chain.

    Measured on one TPU v5e (PERF.md section 5-6, PR 38; traced pairs, the
    parent's dynamic step first).  ``epsilon-cocoa-plus.dense-rounds`` (8192
    chains x 49 rows): ``cocoa_steps_s`` 0.022247 -> 0.000413 of a round of
    0.031287 -> 0.008968 s.  ``rcv1-cocoa.cocoa-rounds`` (8192 chains x 83
    rows): 0.037162 -> 0.001446 of 0.803775 -> 0.767115.  The kernel alone
    takes 0.39 and 1.38 ms; the hoisted draws 0.25 ms."""
    if (platform != "tpu" or inner != "gram"
            or jnp.dtype(dtype) != jnp.float32):
        return "dynamic"
    from .sdca_pallas import fits_vmem  # pallas: a second of import

    return "kernel" if fits_vmem(h_rows, steps) else "dynamic"


def _resolve_inner(problem: BlockedSVMProblem, config: SVMConfig,
                   mesh: Mesh) -> str:
    """auto -> gram|scatter, from the per-device (C, H, H) Gram budget
    (FLINK_MS_SVM_GRAM_BYTES, default 1 GiB).  Resolved BEFORE the fit
    cache key is built, so the env var keys the executable exactly when it
    can affect it."""
    if config.inner != "auto":
        return config.inner
    D = num_blocks(mesh)
    C = _round_up(problem.n_blocks, D) // D
    H = problem.rows_per_block
    gram_bytes = C * H * H * np.dtype(config.dtype).itemsize
    limit = int(os.environ.get("FLINK_MS_SVM_GRAM_BYTES", 1 << 30))
    return "gram" if gram_bytes <= limit else "scatter"


# ---------------------------------------------------------------------------
# device-side kernel
# ---------------------------------------------------------------------------

def _combine_scales(config: SVMConfig, K: int):
    """-> (γ, σ'): what a round's combination multiplies the chains'
    summed updates by, and the smoothing of the local subproblem."""
    if config.mode == "avg":
        return config.stepsize / K, 1.0  # averaged combination (CoCoA-v1)
    # added combination (CoCoA+); safe default σ' = γK
    return config.stepsize, (
        config.sigma_prime if config.sigma_prime is not None
        else config.stepsize * K)


def chain_sdca_gram(wx0, gram_c, label_c, sqn_c, alpha_c, key_c, *,
                    steps: int, lam_n: float, sigma_p: float):
    """``steps`` serial SDCA steps of ONE chain, Gram-matrix inner loop: the
    running margin vector wx[i] = w_loc·x_i absorbs each update via
    one Gram row (wx += σ'·Δα_j/λn · G[j, :]), so no step touches the
    (d,)-dim weights.  Same RNG and dual step as ``chain_sdca`` —
    identical update sequence, reassociated arithmetic."""
    def sdca_step(h, inner_c):
        wx, a = inner_c
        j = jax.random.randint(jax.random.fold_in(key_c, h), (), 0,
                               label_c.shape[0])
        y = label_c[j]
        qii = sqn_c[j]
        a_j = a[j]
        grad = 1.0 - y * wx[j]
        new_dual = jnp.clip(
            a_j * y + grad * lam_n / (sigma_p * jnp.maximum(qii, 1e-12)),
            0.0, 1.0,
        )
        delta = jnp.where(qii > 0, y * new_dual - a_j, 0.0)
        a = a.at[j].add(delta)
        wx = wx + (sigma_p * delta / lam_n) * gram_c[j]
        return wx, a

    _, a = jax.lax.fori_loop(0, steps, sdca_step, (wx0, alpha_c))
    return a - alpha_c


def hoisted_draws(keys, steps: int, rows: int):
    """The rows ``chain_sdca_gram`` draws, step by step, for every chain at
    once: ``(C, steps)`` int32 from the chains' keys, the same
    ``fold_in(key_c, h)`` sequence, so that a step outside the loop that
    draws (the Pallas kernel) runs the identical update sequence."""
    return jax.vmap(lambda key_c: jax.vmap(
        lambda h: jax.random.randint(
            jax.random.fold_in(key_c, h), (), 0, rows)
    )(jnp.arange(steps)))(keys)


def _make_fit(problem: BlockedSVMProblem, config: SVMConfig, mesh: Mesh,
              head: int = 0):
    D = num_blocks(mesh)
    K = problem.n_blocks               # real logical blocks
    C = _round_up(K, D) // D           # chains stacked per device
    n = problem.n_examples
    lam = config.regularization
    H = config.local_iterations
    lam_n = lam * max(n, 1)
    dtype = config.dtype
    gamma, sigma_p = _combine_scales(config, K)

    H_rows = problem.rows_per_block
    d = problem.n_features
    dense = problem.dense
    inner = _resolve_inner(problem, config, mesh)
    platform = mesh.devices.flat[0].platform
    in_kernel = resolve_step(platform, inner, dtype, H_rows, H) == "kernel"
    if in_kernel:
        from .sdca_pallas import LANES, SUBLANES, sdca_steps_lanes

        # the kernel's geometry: rows on the sublanes, chains on the lanes
        Hp, Cp = _round_up(H_rows, SUBLANES), _round_up(C, LANES)

        def to_lanes(x, rows=Hp):
            """(C, ·) of a device's chains -> (rows, Cp), zero pads."""
            return jnp.pad(x.T, ((0, rows - x.shape[1]), (0, Cp - C)))
    # the Gram engine's sparse rows: tiles, beside a head of `head` columns
    tiled = inner == "gram" and not dense

    def chain_sdca(w, idx_c, val_c, label_c, sqn_c, alpha_c, key_c,
                   row0_c=None):
        """H serial SDCA steps of ONE chain; vmapped over the C chains of a
        device so every step is a (C, L)-wide gather/compute/scatter.  On
        the dense layout ``idx_c`` is None, ``val_c`` is the device's whole
        ``(C·rows, d)`` matrix and the chain's rows start at ``row0_c``: a
        step reads one dense row a chain and touches all of w_loc."""
        rows = label_c.shape[0]

        def sdca_step(h, inner):
            w_loc, a = inner
            j = jax.random.randint(jax.random.fold_in(key_c, h), (), 0, rows)
            if idx_c is None:
                x, w_x = val_c[row0_c + j], w_loc
            else:
                ids = idx_c[j]
                x, w_x = val_c[j], jnp.take(w_loc, ids)
            y = label_c[j]
            qii = sqn_c[j]
            # elementwise f32 on both layouts: no matmul precision applies
            wx = jnp.sum(w_x * x)
            grad = 1.0 - y * wx
            # closed-form hinge dual step on the σ'-smoothed local
            # subproblem, clipped to the box [0, 1]
            a_j = a[j]
            new_dual = jnp.clip(
                a_j * y + grad * lam_n / (sigma_p * jnp.maximum(qii, 1e-12)),
                0.0, 1.0,
            )
            delta = jnp.where(qii > 0, y * new_dual - a_j, 0.0)
            a = a.at[j].add(delta)
            # the chain-local view carries σ' (CoCoA+ models the quadratic
            # coupling of its OWN updates σ'-fold, so later coordinates in
            # the chain see the smoothed effect); σ' = 1 in avg mode
            step = sigma_p * delta * x / lam_n
            w_loc = w_loc + step if idx_c is None else w_loc.at[ids].add(step)
            return w_loc, a

        w_loc, a = jax.lax.fori_loop(0, H, sdca_step, (w, alpha_c))
        # Δw of this chain under the TRUE coupling: (w_loc − w)/σ'
        return (w_loc - w) / sigma_p, a - alpha_c

    sdca_gram = functools.partial(
        chain_sdca_gram, steps=H, lam_n=lam_n, sigma_p=sigma_p)

    def gram_out(gram):
        """The Gram build's (C, H, H) as the step reads it: as it is, or
        for the kernel chain-minor, (H_rows, Hp, Cp), inside the build's own
        program, so that no chain-major copy outlives it."""
        if not in_kernel:
            return gram
        return jnp.pad(jnp.transpose(gram, (1, 2, 0)),
                       ((0, 0), (0, Hp - H_rows), (0, Cp - C)))

    def build_gram(idx_s, val_s):
        """Per-chain row-Gram G[c] = S_c S_cᵀ via densify-matmul: scatter
        one chain's L-padded sparse rows into an (H, d) dense staging
        buffer and take the (H, H) product on the MXU.  lax.map chunking
        bounds the staging transient; pad rows/slots have val 0 and
        contribute nothing.  One-time cost per fit call."""
        rows_ar = jnp.arange(H_rows)
        B = max(int(
            (256 << 20) // max(H_rows * d * np.dtype(dtype).itemsize, 1)
        ), 1)

        def one(args):
            idx_c, val_c = args
            dense = jnp.zeros((H_rows, d), dtype).at[
                rows_ar[:, None], idx_c
            ].add(val_c)
            return jnp.einsum("id,jd->ij", dense, dense,
                              precision="highest",
                              preferred_element_type=dtype)

        with jax.named_scope("svm.gram"):
            return gram_out(jax.lax.map(one, (idx_s, val_s), batch_size=B))

    def build_gram_dense(val):
        """Per-chain row-Gram G[c] = X_c X_cᵀ straight from the device's
        dense ``(C·H, d)`` rows: a batched product over a step of chains at
        a time.  The steps bound the transient: a step's rows are copied
        out as ``(chains, H, d)``, whose tiles pad H to a multiple of 8,
        which is why X itself is kept flat.  The last step starts where it
        ends on the last chain, so every step has one shape; the chains it
        shares with the step before are computed twice and kept once."""
        B = min(max(int((256 << 20)
                        // max(H_rows * d * np.dtype(dtype).itemsize, 1)),
                    1), C)
        steps = -(-C // B)

        def step(first):
            x = jax.lax.dynamic_slice_in_dim(
                val, first * H_rows, B * H_rows).reshape(B, H_rows, d)
            return jnp.einsum("chd,cgd->chg", x, x,
                              precision=_DENSE_PRECISION,
                              preferred_element_type=dtype)

        with jax.named_scope("svm.gram"):
            gram = jax.lax.map(
                step, jnp.minimum(jnp.arange(steps) * B, C - B))
            return gram_out(jnp.concatenate([
                gram[:-1].reshape(-1, H_rows, H_rows),
                gram[-1, steps * B - C:]]))

    def block_fit(span, w0, idx, val, label, sq_norm, alpha0, seed_arr,
                  gram=None, slot=None, row0=None, n_tiles=None,
                  head_val=None, head_ids=None):
        # dense layout, both engines: idx is None and val the device's
        # (C·rows, d) matrix.  Sparse layout, scatter engine: idx, val are
        # the device's padded (C, rows, L) rectangles.  Gram engine: they
        # are the tail's tiles, (1, tiles, step, rows) each (_tail_tiles),
        # slot the flat (C·rows) slot of every tile row, row0 the first of
        # them under each tile, n_tiles the tiles that hold an entry, and
        # with a head (head > 0) head_val its (C·rows, head) block and
        # head_ids its columns' feature ids
        # span = [start, stop): rounds run with ABSOLUTE indices so the
        # per-round RNG (fold_in of the round number) is identical whether
        # the caller runs one long fit or chains warm-started segments
        # per-device shards: alpha (C, rows); w0 replicated
        device_id = jax.lax.axis_index(BLOCK_AXIS)

        def chain_keys(it):
            # chain RNG: globally unique (seed, global chain id, round)
            chain_ids = device_id * C + jnp.arange(C)
            return jax.vmap(
                lambda c: jax.random.fold_in(
                    jax.random.fold_in(
                        jax.random.PRNGKey(seed_arr[0]), c
                    ),
                    it,
                )
            )(chain_ids)

        # The round's parts carry named scopes (svm.margins, svm.steps,
        # svm.dw, svm.combine) so that a profile splits a round by them;
        # the scatter engine computes its margins inside each step, so its
        # rounds have no svm.margins.
        def outer(it, carry):
            w, alpha = carry
            with jax.named_scope("svm.steps"):
                keys = chain_keys(it)
                if dense:
                    dw, dalpha = jax.vmap(
                        chain_sdca, in_axes=(None, None, None, 0, 0, 0, 0, 0)
                    )(w, None, val, label, sq_norm, alpha, keys,
                      jnp.arange(C) * H_rows)
                else:
                    dw, dalpha = jax.vmap(
                        chain_sdca, in_axes=(None, 0, 0, 0, 0, 0, 0)
                    )(w, idx, val, label, sq_norm, alpha, keys)
            with jax.named_scope("svm.dw"):
                dw = jnp.sum(dw, axis=0)
            with jax.named_scope("svm.combine"):
                w = w + gamma * jax.lax.psum(dw, BLOCK_AXIS)
                alpha = alpha + gamma * dalpha
            return w, alpha

        if tiled:
            row0, n_tiles = row0[0], n_tiles[0, 0]

            def tile_rows(rows, t):
                return jax.lax.dynamic_slice_in_dim(rows, row0[t], _TILE_ROWS)

        def outer_gram(it, carry):
            w, alpha = carry
            # round-start margins for every row: ONE gather of w over the
            # stored entries, reduced over each tile's positions, then placed
            # by slot (rows without entries keep 0).  Elementwise f32, as
            # the scatter path computes its margins: a default-precision
            # (bf16-pass) contraction here would seed every SDCA step with
            # ~1e-3 relative error and break the documented cross-engine
            # equivalence on TPU.
            # The dense layout streams X instead: one product, X w.
            with jax.named_scope("svm.margins"):
                if dense:
                    wx0 = jnp.einsum(
                        "nd,d->n", val, w, precision=_DENSE_PRECISION,
                        preferred_element_type=dtype).reshape(C, H_rows)
                else:
                    # the tail's entries gathered a tile at a time: only
                    # the tiles that hold an entry, whose number is an
                    # operand; the head's columns, where there are any,
                    # streamed
                    def tile_margins(t, sums):
                        part = jnp.sum(
                            jnp.take(w, idx[0, t], axis=0) * val[0, t], axis=0)
                        return jax.lax.dynamic_update_slice_in_dim(
                            sums, tile_rows(sums, t) + part, row0[t], 0)

                    sums = jax.lax.fori_loop(
                        0, n_tiles, tile_margins,
                        jnp.zeros((slot.shape[1],), dtype))
                    wx0 = jnp.zeros((C * H_rows,), dtype).at[slot[0]].add(sums)
                    if head:
                        wx0 = wx0 + jnp.einsum(
                            "nf,f->n", head_val, w[head_ids],
                            precision=_DENSE_PRECISION,
                            preferred_element_type=dtype)
                    wx0 = wx0.reshape(C, H_rows)
            with jax.named_scope("svm.steps"):
                keys = chain_keys(it)
                if in_kernel:
                    # gram, label and sq_norm lie chain-minor already
                    dalpha = sdca_steps_lanes(
                        to_lanes(hoisted_draws(keys, H, H_rows),
                                 _round_up(H, SUBLANES)),
                        gram, to_lanes(wx0), label, sq_norm, to_lanes(alpha),
                        steps=H, lam_n=float(lam_n), sigma_p=float(sigma_p),
                        interpret=platform != "tpu")[:H_rows, :C].T
                else:
                    dalpha = jax.vmap(sdca_gram)(
                        wx0, gram, label, sq_norm, alpha, keys
                    )
            # this device's Δw = Σ_chains X_cᵀ Δα_c / λn: ONE reduction
            # per round (the scatter engine pays one per STEP per chain).
            # Sparse rows: an unsorted scatter-add a tile, 0.380 s of the
            # rcv1 round where sorted segment-sums took 1.223, 0.906 (PR 32).
            with jax.named_scope("svm.dw"):
                dalpha_flat = dalpha.reshape(-1)
                if dense:
                    # the second pass over X: Xᵀ Δα
                    dw = jnp.einsum(
                        "nd,n->d", val, dalpha_flat,
                        precision=_DENSE_PRECISION,
                        preferred_element_type=dtype)
                else:
                    da_rows = dalpha_flat[slot[0]]

                    def tile_dw(t, dw):
                        return dw.at[idx[0, t]].add(
                            val[0, t] * tile_rows(da_rows, t))

                    dw = jax.lax.fori_loop(
                        0, n_tiles, tile_dw, jnp.zeros((d,), dtype))
                    if head:
                        # the head's ids are distinct: one F-element scatter
                        dw = dw.at[head_ids].add(jnp.einsum(
                            "nf,n->f", head_val, dalpha_flat,
                            precision=_DENSE_PRECISION,
                            preferred_element_type=dtype))
                dw = dw / lam_n
            with jax.named_scope("svm.combine"):
                w = w + gamma * jax.lax.psum(dw, BLOCK_AXIS)
                alpha = alpha + gamma * dalpha
            return w, alpha

        body = outer_gram if inner == "gram" else outer
        return jax.lax.fori_loop(span[0], span[1], body, (w0, alpha0))

    spec3 = P(BLOCK_AXIS, None, None)
    spec2 = P(BLOCK_AXIS, None)
    # dense: no ids (None has no leaves), X as (slots, d) split by rows;
    # tiled: the tiles, (devices, tiles, step, rows)
    rows_specs = ((P(), spec2) if dense
                  else (P(BLOCK_AXIS, None, None, None),) * 2 if tiled
                  else (spec3, spec3))
    # the kernel step reads the Gram tensor, the labels and the norms
    # chain-minor: a device's chains on the lanes
    lanes3, lanes2 = P(None, None, BLOCK_AXIS), P(None, BLOCK_AXIS)
    by_chain = lanes2 if in_kernel else spec2
    in_specs = (P(), P(), *rows_specs, by_chain, by_chain, spec2, P())
    if inner == "gram":
        in_specs += (lanes3 if in_kernel else spec3,)  # gram
        if tiled:
            in_specs += (spec2, spec2, spec2)  # slot, row0, n_tiles
            if head:
                in_specs += (spec2, P())  # the block by device, its ids whole
    jfit = jax.jit(shard_map(
        block_fit,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(), spec2),
        check_vma=False,
    ))

    def fit(rounds, *args, start=0):
        """``fit(rounds, *dev_args)`` runs rounds from scratch; pass
        ``start=r0`` (with the w/alpha carried out of a previous segment
        as args[0]/args[5]) to continue EXACTLY where a prior call
        stopped — absolute-round RNG makes chained segments bit-identical
        to one long fit."""
        # the stage is the host's side of the call: the rounds are
        # enqueued, not awaited (their device time lies under the svm.*
        # scopes of the same profile)
        with tracing.stage("svm.fit"):
            lo = jnp.asarray(start, jnp.int32)
            span = jnp.stack([lo, lo + jnp.asarray(rounds, jnp.int32)])
            out = jfit(span, *args)
        obs_metrics.get_registry().counter("tpums_svm_rounds_total").inc(
            int(rounds))
        return out
    # the Gram build is hoisted out of the fit: compile_svm_fit runs it
    # once and ships the (Kp, H, H) tensor as a device arg, so repeat fit
    # calls (benchmark loops, retrain cycles) don't pay it again
    gram_fn = None
    if inner == "gram":
        build, specs = ((build_gram_dense, (spec2,)) if dense
                        else (build_gram, (spec3, spec3)))
        gram_fn = jax.jit(shard_map(
            build, mesh=mesh,
            in_specs=specs,
            out_specs=lanes3 if in_kernel else spec3,
            check_vma=False,
        ))
    return fit, gram_fn, (Hp, Cp) if in_kernel else None


_FIT_CACHE: "dict" = {}
_FIT_CACHE_MAX = 8


def _cached_fit(problem: BlockedSVMProblem, config: SVMConfig, mesh: Mesh,
                head: int = 0):
    """One compiled program per (layout shapes, config-sans-iterations,
    mesh, head columns): repeat fits and benchmark loops skip retracing;
    the round count is a traced argument."""
    inner = _resolve_inner(problem, config, mesh)
    key = (
        mesh,
        head,
        problem.n_blocks,
        problem.rows_per_block,
        problem.val.shape,
        problem.dense,
        problem.n_features,
        problem.n_examples,  # lam_n = lam * n is baked into the program
        config.local_iterations,
        config.regularization,
        config.stepsize,
        config.mode,
        config.sigma_prime,
        str(config.dtype),
        inner,
        resolve_step(mesh.devices.flat[0].platform, inner, config.dtype,
                     problem.rows_per_block, config.local_iterations),
    )
    fn = _FIT_CACHE.pop(key, None)
    if fn is None:
        fn = _make_fit(problem, config, mesh, head)
    _FIT_CACHE[key] = fn  # re-insert: dict order gives LRU eviction
    while len(_FIT_CACHE) > _FIT_CACHE_MAX:
        del _FIT_CACHE[next(iter(_FIT_CACHE))]
    return fn


def _set_layout_gauges(slots: int, stored: int, nonzero: int,
                       gram_bytes: int, chains: int, dense_entries: int,
                       sigma_prime: float, step_kernel: bool,
                       head: int, head_nonzeros: int) -> None:
    """What the compiled round streams, for whoever reads the registry:
    row slots (pad rows and pad blocks included); the entries stored per
    slot, so that rows x row_width is every entry the round's gather and
    scatter-add touch (the scatter engine: the width every row is padded
    to; the Gram engine: a mean over the tail's tiles that hold an entry;
    the dense layout: the feature count, every cell of X); the stored
    entries that carry no value (dense: the pad rows' cells and the zeros
    inside real rows; the Gram engine: of the tail's tiles); the Gram
    tensor's bytes (0 on the scatter engine); chains per device; the cells
    held dense (all of X on the dense layout, the head block's on a sparse
    layout with a head, 0 without one), so that dense_entries over rows x
    row_width says whether the dense layout served a fit; the σ' in force
    (1 in avg mode);
    the chains a device whose SDCA steps the compiled round runs in the
    Pallas kernel (all of them, or 0 on the XLA step: ``resolve_step``);
    the feature columns held dense beside a sparse tail (``head_width``; 0
    where no head was taken), the non-zeros they hold, and all the
    non-zeros of the data, so that head_nonzeros over nonzeros is the share
    of the entries that left the gather and the scatter-add."""
    reg = obs_metrics.get_registry()
    reg.gauge("tpums_svm_rows").set(slots)
    reg.gauge("tpums_svm_row_width").set(stored / slots)
    reg.gauge("tpums_svm_pad_entries").set(stored - (nonzero - head_nonzeros))
    reg.gauge("tpums_svm_gram_bytes").set(gram_bytes)
    reg.gauge("tpums_svm_chains_per_device").set(chains)
    reg.gauge("tpums_svm_step_kernel_chains").set(chains if step_kernel else 0)
    reg.gauge("tpums_svm_dense_entries").set(dense_entries)
    reg.gauge("tpums_svm_sigma_prime").set(sigma_prime)
    reg.gauge("tpums_svm_head_columns").set(head)
    reg.gauge("tpums_svm_head_nonzeros").set(head_nonzeros)
    reg.gauge("tpums_svm_nonzeros").set(nonzero)


def layout_report() -> str:
    """One clause for the trainer's ``[SVM]`` line, from the gauges the
    last ``compile_svm_fit`` set: which row layout it chose, what the
    layout stores, the σ' in force, and the form of the SDCA steps."""
    reg = obs_metrics.get_registry()
    (rows, width, dense_cells, gram_bytes, sigma, in_kernel, head, head_nnz,
     nnz) = (reg.gauge("tpums_svm_" + name).value for name in (
         "rows", "row_width", "dense_entries", "gram_bytes", "sigma_prime",
         "step_kernel_chains", "head_columns", "head_nonzeros", "nonzeros"))
    if head:
        layout = (f"sparse rows split by column ({int(head)} head columns "
                  f"held dense with {100 * head_nnz / max(nnz, 1):.1f}% of "
                  f"the non-zeros, tail {int(rows)} x {width:.2f} stored "
                  "entries in tiles)")
    elif dense_cells:
        layout = f"dense rows ({int(rows)} x {int(width)} cells, no ids)"
    else:
        # the Gram engine (its tensor has bytes) holds sparse rows in tiles
        layout = (f"sparse rows ({int(rows)} x {width:.2f} stored entries, "
                  + ("in tiles)" if gram_bytes else "one padded rectangle)"))
    return (f"layout {layout}, sigma' {sigma:g}, steps "
            + ("in the Pallas kernel" if in_kernel else "in XLA"))


def compile_svm_fit(
    problem: BlockedSVMProblem, config: SVMConfig, mesh: Mesh,
    head_columns: Optional[int] = None,
):
    """-> (fit_fn, dev_args): the compiled CoCoA program plus device-
    resident sharded inputs.  ``fit_fn(iterations, *dev_args)`` -> (w,
    alpha shards).  Benchmarks call ``fit_fn`` directly so host<->device
    transfer and compile stay out of the timed region.  ``dev_args[0]`` is
    w and ``dev_args[5]`` alpha, ``(Kp, rows_per_block)`` in slot order;
    [3] and [4] are the labels and squared norms, in that shape too or, where
    the SDCA steps run in the Pallas kernel (``resolve_step``), chain-minor
    beside the Gram tensor at [7], ``(rows, rows8, chains128)`` a device;
    the rest is the layout's and the engine's: the padded rectangles at
    [1], [2] on the scatter engine, the rows' tiles on the Gram engine
    (``_tail_tiles``; [8] their rows' slots, [9] each tile's first row,
    [10] the tiles that hold an entry);
    on the dense layout [1] is None and [2] is X as ``(Kp·rows, features)``,
    placed once for the Gram build and the rounds alike.  Phases, each
    awaited: ``svm.gram_build`` (Gram engine only) and ``svm.place``, with
    the host's copy into tiles under it as ``svm.bucket`` (the dense layout
    opens ``svm.place`` twice, for X before the Gram build and for the
    small arrays after it, and cuts no tiles).

    The Gram engine's sparse layout is split by column where
    ``head_width`` says so, from the columns' counts, the itemsize and the
    device's reported memory (``head_columns`` names a width instead: for
    tests): the hottest columns are one dense ``(Kp·rows, F)`` block at
    [11], their feature ids at [12], built on the device from a compact
    list of their entries (phase ``svm.head`` under ``svm.place``), and the
    tiles hold only the other columns' entries.  f32 state only: bf16
    state takes no head, and a fit without one has no [11], [12]."""
    D = num_blocks(mesh)
    Kp = _round_up(problem.n_blocks, D)
    dtype = config.dtype
    slots = Kp * problem.rows_per_block
    head = 0
    if (not problem.dense and _resolve_inner(problem, config, mesh) == "gram"
            and jnp.dtype(dtype) == jnp.float32):
        head = (head_width(problem.col_count / D, slots // D,
                           jnp.dtype(dtype).itemsize,
                           device_memory(mesh.devices.flat[0]))
                if head_columns is None else head_columns)
        if not 0 <= head <= problem.n_features:
            raise ValueError(
                f"head_columns={head}: the layout has "
                f"{problem.n_features} feature columns")
    shard3 = block_sharding(mesh, rank=3)
    shard2 = block_sharding(mesh, rank=2)
    rep = NamedSharding(mesh, P())

    def put(a, sharding, as_dtype=None):
        return jax.device_put(jnp.asarray(a, dtype=as_dtype), sharding)

    if problem.dense:
        with tracing.phase("svm.place"):
            # straight from the host matrix to its shards: no staging copy
            # on the default device.  X sets out before the program is
            # looked up: on a TPU the lookup imports the Pallas step's
            # module, a second that the transfer flies under
            idx, val = None, jax.device_put(
                _pad_blocks(problem.val, Kp).reshape(
                    -1, problem.n_features), shard2).astype(dtype)
            made = _cached_fit(problem, config, mesh)
            jax.block_until_ready(val)
    else:
        made = _cached_fit(problem, config, mesh, head)
        idx, val = _pad_blocks(problem.idx, Kp), _pad_blocks(problem.val, Kp)
    fit, gram_fn, step_lanes = made
    tiled = gram_fn is not None and not problem.dense
    stored, extra, head_nonzeros = val.size, [], 0
    if gram_fn is not None:
        # of sparse rows the Gram build reads the padded rectangles once
        # and lets them go: the rounds hold the tiles only
        with tracing.phase("svm.gram_build"):
            extra.append(jax.block_until_ready(gram_fn(*(
                (val,) if problem.dense
                else (put(idx, shard3), put(val, shard3, dtype))))))

    def by_chain(a):
        """Labels or squared norms, (K, rows) -> the device: (Kp, rows)
        in slot order, or chain-minor as the kernel step reads them, (Hp,
        D·Cp), a device's chains on its lanes, zero pads."""
        a = _pad_blocks(a, Kp)
        if not step_lanes:
            return put(a, shard2, dtype)
        Hp, Cp = step_lanes
        a = a.reshape(D, Kp // D, -1).transpose(2, 0, 1)
        a = np.pad(a, ((0, Hp - a.shape[0]), (0, 0), (0, Cp - Kp // D)))
        return put(a.reshape(Hp, D * Cp),
                   NamedSharding(mesh, P(None, BLOCK_AXIS)), dtype)
    # the phases end when the device has what they made, so that a profile
    # shows the Gram build and the transfer, not their dispatch
    with tracing.phase("svm.place"):
        if tiled:
            # without a head a row's tail is the row
            row_len = tail_len = _pad_blocks(problem.row_len, Kp).reshape(-1)
            with tracing.phase("svm.bucket"):
                if head:
                    tail_len = _tail_lengths(
                        idx.reshape(slots, -1), row_len, problem.col_rank,
                        head)
                ids_t, val_t, slot, row0, n_tiles = _tail_tiles(
                    idx, val, row_len, tail_len, D)
            extra += [put(a, shard2) for a in (slot, row0, n_tiles)]
            stored = int(n_tiles.sum()) * _TILE_STEP * _TILE_ROWS
            if head:
                with tracing.phase("svm.head"):
                    block, head_nonzeros = _place_head(
                        mesh, idx, val, row_len, tail_len, problem.col_rank,
                        head, dtype)
                extra += [block, put(np.argsort(problem.col_rank)[:head], rep,
                                     jnp.int32)]
            idx, val = ids_t, val_t
        shard_rows = block_sharding(mesh, rank=4) if tiled else shard3
        dev_args = jax.block_until_ready([
            put(np.zeros((problem.n_features,)), rep, dtype),
            idx if problem.dense else put(idx, shard_rows),
            val if problem.dense else put(val, shard_rows, dtype),
            by_chain(problem.label),
            by_chain(problem.sq_norm),
            put(np.zeros((Kp, problem.rows_per_block)), shard2, dtype),
            put(np.asarray([config.seed], np.uint32), rep),
            *extra,
        ])
    _set_layout_gauges(
        slots, stored, int(np.count_nonzero(problem.val)),
        extra[0].nbytes if extra else 0, Kp // D,
        stored if problem.dense else slots * head,
        _combine_scales(config, problem.n_blocks)[1], bool(step_lanes),
        head, head_nonzeros)
    return fit, dev_args


@functools.lru_cache(maxsize=_FIT_CACHE_MAX)
def _head_scatter(mesh: Mesh, window: int):
    """The program that adds one piece of (row, column, value) triples to
    a window of a device's rows of the head block, the block donated and
    updated in place."""
    def scatter(block, first, rows, cols, vals):
        part = jax.lax.dynamic_slice_in_dim(block, first[0, 0], window)
        return jax.lax.dynamic_update_slice_in_dim(
            block, part.at[rows[0], cols[0]].add(vals[0]), first[0, 0], 0)

    by_rows = P(BLOCK_AXIS, None)
    return jax.jit(shard_map(
        scatter, mesh=mesh, in_specs=(by_rows,) * 5, out_specs=by_rows,
        check_vma=False), donate_argnums=0)


def _place_head(mesh: Mesh, idx, val, row_len, tail_len, col_rank,
                head: int, dtype):
    """-> (block, its non-zeros): the head's dense block on the devices,
    (slots, head) split by rows as the dense layout's X is: zeroed there,
    then the pieces of the compact entry list (``_head_entries``) scattered
    one after the other into windows of ``_HEAD_WINDOW`` rows (of all a
    device's rows on small data), by one program whatever their number.
    The host never holds the block."""
    D = num_blocks(mesh)
    window = min(_HEAD_WINDOW, row_len.size // D)
    entries = _head_entries(idx, val, row_len, tail_len, col_rank, D, window)
    by_rows = block_sharding(mesh, rank=2)
    block = jax.jit(lambda: jnp.zeros((row_len.size, head), dtype),
                    out_shardings=by_rows)()
    scatter = _head_scatter(mesh, window)
    for piece in zip(*(a.transpose(1, 0, 2) for a in entries)):
        block = scatter(block, *(jax.device_put(a, by_rows) for a in piece))
    return block, int(np.count_nonzero(entries[3]))


def svm_fit(
    data: SparseData,
    config: SVMConfig,
    mesh: Mesh,
    problem: Optional[BlockedSVMProblem] = None,
) -> SVMModel:
    """Train the CoCoA linear SVM; returns the dense primal weight vector
    (the reference's ``weightsOption: DataSet[DenseVector]``,
    SVMImpl.scala:31-35)."""
    D = num_blocks(mesh)
    if problem is None:
        problem = prepare_svm_blocked(data, D, seed=config.seed)
    fit, dev_args = compile_svm_fit(problem, config, mesh)
    w, _alpha = fit(config.iterations, *dev_args)
    from ..parallel.distributed import to_host_array

    return SVMModel(weights=to_host_array(w).astype(np.float64))
