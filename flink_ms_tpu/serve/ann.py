"""IVF approximate-nearest-neighbor tier for the retrieval plane.

The exact TOPK scan is linear in catalog size; past ~10M rows the scan
itself is the latency floor no matter how it is sharded.  This module
makes retrieval cost sublinear with the classic IVF (inverted-file)
recipe, adapted for maximum-inner-product retrieval over ALS item
factors:

- **Build** (off the query path, on the rebuild thread): a coarse k-means
  quantizer over the item factors, trained on the device with a jitted
  Lloyd's iteration over a bounded sample, then ONE assignment pass over
  the resident matrix in chunks (no host transfer).  The rows are then
  laid out IN LIST ORDER (gathered on the host and put in strips): a list
  is a run of whole blocks of ``block_rows`` contiguous rows, its last
  block padded with zero rows,
  and the index owner installs that matrix as THE resident matrix
  (``DeviceFactorIndex`` keeps ``id -> position``, so an UPDATE of a row
  lands in its block and the next query scores the new value).  Every
  row is in exactly one list, whatever the lists' lengths; nothing is
  dropped.  The block count is the bound ``ceil(n / block_rows) + nlist``
  (each list wastes less than one block), so no shape depends on the
  data; blocks past the last list are never read.
- **Query** (one jitted program a batch bucket, three named scopes):
  ``topk.ivf.probe`` scores the frame against the ``nlist`` centroids
  (inner product, the retrieval metric), takes each query's ``nprobe``
  best lists and writes the frame's work list: the ids of the blocks of
  every list that ANY query of the frame probes, in ascending order, and
  per list the set of queries that probe it (one int32 of bits).
  ``topk.ivf.scan`` is a Pallas kernel over that work list, its grid as
  long as the list (a dynamic bound): a grid step reads ``_STEP_BLOCKS``
  blocks as contiguous runs of rows (the block ids are scalar-prefetched,
  the pipeline fetches the next step's blocks under this one's product),
  scores them against the whole frame on the MXU at the score precision
  and keeps each query's MAXIMUM per block.  ``topk.ivf.select`` takes the
  ``k`` blocks with the best maxima per query (the top ``k`` rows lie in
  them: a row outside has ``k`` block maxima above it), re-scores those
  blocks in a second small kernel and takes the exact top ``k`` of
  ``k * block_rows`` scores.  No per-row gather anywhere; a block read
  once serves every query of the frame, so a frame costs the UNION of its
  queries' lists and never more than the whole catalog.
  The only approximation IVF introduces is a missing candidate; scores
  of returned items are exact by construction.
- **Contract**: the build measures recall@k of this program over the
  list-ordered matrix against the exact tier's plain program over the
  row-ordered one (both resident: no further copy of the catalog) on a
  probe of catalog rows, in frames no wider than the batcher's, and
  records it (``recall_probe``): what probing loses, and any fault of the
  kernels or the layout, of which the plain program shares nothing.  The
  index owner gates on it (``TPUMS_ANN_RECALL_MIN``, see ``topk.py``) —
  the approximation is a measured contract, not a hope.

Sizing rule of thumb (also in README):  ``nlist ~ 4*sqrt(n)`` rounded to
a power of two keeps lists ~``sqrt(n)/4`` long; ``nprobe = nlist/16``
then scans ~``n/16`` of the catalog for recall@100 in the 0.95+ range on
clustered factor geometries.  Knobs: ``TPUMS_ANN_NLIST``,
``TPUMS_ANN_NPROBE``.  The tier lives on one device: a mesh-sharded
catalog keeps the sharded exact tier.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional

import numpy as np

from ..obs.tracing import phase
from . import topk as _topk
from .topk import _PAD_SCORE, _pack_results, _target_device, _unpack_results

# rows per assignment dispatch (one compiled shape).  The distance matrix
# a dispatch materializes is (chunk, nlist) f32 — 32k rows x 4096 lists is
# a bounded 512 MB peak even at the 10M-row catalog's default sizing;
# an unchunked pass would be O(n * nlist) and OOM the build.
_ASSIGN_CHUNK = 1 << 15
# rows a strip of the list-ordered matrix holds on its way to the device
# (0.84 GB of 200-wide rows)
_PUT_STRIP = 1 << 20
# blocks a grid step of the scan reads (one input each, so that the
# pipeline's fixed cost a step is paid once per _STEP_BLOCKS * block_rows
# rows); divides the 128 lanes of an output tile
_STEP_BLOCKS = 4
_LANES = 128
# a block's fill rides in the low bits of its list's id (block_rows <= 256)
_FILL_BITS = 9
# widest frame: a list's probing queries are the bits of one int32
_MAX_FRAME = 32


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _whole_chunks(n: int, size: int):
    """-> (chunk, starts): ``n`` rows covered by chunks of one size (one
    compiled shape); the last chunk starts early enough to be whole and
    rewrites what the one before it gave."""
    chunk = min(size, n)
    return chunk, list(range(0, n - chunk, chunk)) + [n - chunk]


def _padded(q: np.ndarray) -> np.ndarray:
    """A frame of up to ``_MAX_FRAME`` queries as one of 8, 16 or 32, filled
    with its first row (which probes nothing new)."""
    b = q.shape[0]
    bp = max(8, _pow2(b))
    if bp == b:
        return q
    return np.concatenate([q, np.broadcast_to(q[:1], (bp - b, q.shape[1]))])


def block_rows(n: int, nlist: int) -> int:
    """Rows in a block of the list layout, from the sizes alone: 256 where
    lists average 1024 rows or more (a block is then 205 KB of 200-wide f32
    rows, and a list wastes a tenth of itself in its last block), else 128,
    the least the scan's product takes on its lane axis."""
    return 256 if n >= 1024 * nlist else 128


def block_count(n: int, nlist: int) -> int:
    """Blocks the list-ordered matrix has: every list ends in a block of its
    own, so ``ceil(n / R) + nlist`` holds any assignment; a whole number of
    output tiles of the scan."""
    return _round_up(-(-n // block_rows(n, nlist)) + nlist, _LANES)


def _in_list_order(rows: np.ndarray, src: np.ndarray, place, dev,
                   strip: int = _PUT_STRIP, threads: int = 8):
    """``rows[src]`` as a device matrix, zeros where ``src`` is -1, without
    a second copy of the catalog on the host: one strip of ``strip`` rows
    at a time is gathered on the host (``np.take`` in threads, it lets go
    of the interpreter lock), put, and written into the device buffer in
    place (``place``: a donated ``dynamic_update_slice``) while the next
    strip is gathered.

    On the host, because the device keeps the matrix column-major and its
    gather of whole rows compiles to 11 GB of temporaries at 5M x 200
    beside the 8.9 GB of operand and result.  In strips, because ONE
    ``device_put`` of the 4.85 GB list-ordered matrix took 26 s on a TPU
    v5e where 4.0 GB take 0.4 (PERF.md section 6, PR 39)."""
    import jax
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor

    n_pad, d = len(src), rows.shape[1]
    strip, starts = _whole_chunks(n_pad, strip)
    piece = -(-strip // threads)

    def gather(lo):
        out = np.empty((strip, d), np.float32)

        def fill(at):
            part = src[lo + at:lo + min(at + piece, strip)]
            view = out[at:at + len(part)]
            np.take(rows, np.maximum(part, 0), axis=0, out=view)
            view[part < 0] = 0.0

        list(pool.map(fill, range(0, strip, piece)))
        return out

    listed = jnp.zeros((n_pad, d), jnp.float32, device=dev)
    with ThreadPoolExecutor(threads) as pool:
        for lo in starts:
            listed = place(listed, jax.device_put(gather(lo), dev),
                           np.int32(lo))
    return listed


# -- the build's programs -------------------------------------------------------


def _build_jits():
    """The build's jitted programs, created on first use (keeps jax import
    off the module path — this file is imported by knob probes that never
    touch a device)."""
    global _BUILD
    if _BUILD is not None:
        return _BUILD
    import jax
    import jax.numpy as jnp

    def nearest(x, cent):
        # argmin ||x-c||^2 == argmin (||c||^2 - 2 x.c)
        d2 = jnp.sum(cent * cent, axis=1)[None, :] - 2.0 * (x @ cent.T)
        return jnp.argmin(d2, axis=1).astype(jnp.int32)

    @jax.jit
    def lloyd(x, cent):
        """One Lloyd round over the training sample ``x``; empty clusters
        keep their old centroid (re-seeding would make the refresh
        non-deterministic for no measured recall gain)."""
        assign = nearest(x, cent)
        nlist = cent.shape[0]
        sums = jax.ops.segment_sum(x, assign, num_segments=nlist)
        counts = jax.ops.segment_sum(
            jnp.ones((x.shape[0],), x.dtype), assign, num_segments=nlist)
        return jnp.where(counts[:, None] > 0,
                         sums / jnp.maximum(counts, 1.0)[:, None], cent)

    @partial(jax.jit, static_argnums=3)
    def assign_chunk(matrix, cent, start, chunk):
        x = jax.lax.dynamic_slice_in_dim(matrix, start, chunk, axis=0)
        return nearest(x, cent)

    @partial(jax.jit, donate_argnums=0)
    def place(listed, strip, start):
        return jax.lax.dynamic_update_slice_in_dim(listed, strip, start, axis=0)

    @partial(jax.jit, static_argnums=2)
    def exact_frame(matrix, q, k):
        """The exact tier's frame program (``topk.topk_many_fn``) over the
        row-ordered matrix: what the recall probe holds the tier against."""
        scores = jnp.matmul(q, matrix.T, precision=_topk._SCORE_PRECISION)
        return jax.lax.top_k(scores, k)[1]

    _BUILD = (lloyd, assign_chunk, place, exact_frame)
    return _BUILD


_BUILD = None


# -- the query program ----------------------------------------------------------


def _scan_kernel(blk_id_ref, meta_ref, bits_ref, total_ref, q_ref, *refs,
                 precision):
    """One grid step: ``_STEP_BLOCKS`` blocks of the work list against the
    frame -> each query's maximum in each block, one lane a block."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    *blk_refs, out_ref = refs
    t = pl.program_id(0)
    bp = q_ref.shape[0]
    rows = blk_refs[0].shape[1]
    first = t * _STEP_BLOCKS
    lane = jax.lax.broadcasted_iota(jnp.int32, (bp, _LANES), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (bp, rows), 1)
    query = jax.lax.broadcasted_iota(jnp.int32, (bp, 1), 0)

    @pl.when(first % _LANES == 0)
    def _():
        out_ref[...] = jnp.full((bp, _LANES), _PAD_SCORE, jnp.float32)

    out = out_ref[...]
    for j, blk_ref in enumerate(blk_refs):
        step = first + j
        meta = meta_ref[blk_id_ref[step]]
        fill = meta & ((1 << _FILL_BITS) - 1)
        bits = bits_ref[meta >> _FILL_BITS]
        s = jnp.dot(q_ref[...], blk_ref[...], precision=precision,
                    preferred_element_type=jnp.float32)      # (Bp, rows)
        best = jnp.max(jnp.where(row < fill, s, _PAD_SCORE), axis=1,
                       keepdims=True)
        mine = (((bits >> query) & 1) == 1) & (step < total_ref[0])
        out = jnp.where(lane == step % _LANES,
                        jnp.where(mine, best, _PAD_SCORE), out)
    out_ref[...] = out


def _rescore_kernel(sel_ref, q_ref, blk_ref, out_ref, *, per_query, precision):
    """One chosen block against the frame; the row of the query that chose
    it is kept (a sum with exact zeros)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    del sel_ref
    b = pl.program_id(0) // per_query
    s = jnp.dot(q_ref[...], blk_ref[...], precision=precision,
                preferred_element_type=jnp.float32)
    query = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    out_ref[...] = jnp.sum(jnp.where(query == b, s, 0.0), axis=0,
                           keepdims=True)


def _search_jit():
    global _SEARCH
    if _SEARCH is not None:
        return _SEARCH
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    @partial(jax.jit, static_argnames=("k", "nprobe", "rows", "interpret"))
    def search(cent, list_blocks, list_rows, blk_meta, matrix, q, *,
               k, nprobe, rows, interpret):
        """``q``: a frame of 8, 16 or 32 queries -> ``(B, 2k + 2)`` int32:
        ``_pack_results`` of (scores, idx) and two columns of counts: the
        rows in the union of the frame's probed lists (the same in every
        row) and the rows scored for this row's query (whole blocks, pads
        included)."""
        precision = _topk._SCORE_PRECISION
        nlist, d = cent.shape
        n_blocks = matrix.shape[0] // rows
        bp = q.shape[0]

        # the device keeps a tall matrix of 200-wide rows column-major (rows
        # along the lanes, no padding of the 200), so its transpose is the
        # same bytes row-major, which is what a kernel's operand has to be:
        # a block of rows is a (d, rows) slab of whole tiles
        by_column = matrix.T
        with jax.named_scope("topk.ivf.probe"):
            cs = jnp.matmul(q, cent.T, precision=precision)   # (Bp, nlist)
            kth = jax.lax.top_k(cs, nprobe)[0][:, -1:]
            probed = cs >= kth            # each query's nprobe best lists
            bits = jnp.sum(
                probed.astype(jnp.int32) << jnp.arange(bp)[:, None], axis=0)
            active = bits != 0
            cnt = jnp.where(active, list_blocks, 0)
            before = jnp.cumsum(cnt) - cnt
            total = jnp.sum(cnt)
            # the blocks of the active lists, in order: a list's blocks are
            # consecutive, so the t-th block of the work list is t plus the
            # inactive blocks before its list, which only grows with t
            skipped = (jnp.cumsum(list_blocks) - list_blocks) - before
            marks = jnp.zeros((n_blocks + 1,), jnp.int32).at[
                jnp.where(cnt > 0, before, n_blocks)].max(skipped)
            step = jnp.arange(n_blocks, dtype=jnp.int32)
            blk_id = step + jax.lax.cummax(marks[:n_blocks], axis=0)
            # past the end the last block again: no fetch, masked in the scan
            blk_id = jnp.where(step < total, blk_id,
                               blk_id[jnp.maximum(total - 1, 0)])
            union_rows = jnp.sum(jnp.where(active, list_rows, 0))
            # rows scored for each query, its blocks' pads included
            probed_rows = rows * jnp.sum(
                jnp.where(probed, list_blocks[None, :], 0), axis=1)

        with jax.named_scope("topk.ivf.scan"):
            steps = jnp.maximum(-(-total // _STEP_BLOCKS), 1)
            def block(j):
                return pl.BlockSpec(
                    (d, rows),
                    lambda t, ids, *_: (0, ids[t * _STEP_BLOCKS + j]))

            best = pl.pallas_call(
                partial(_scan_kernel, precision=precision),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=4,
                    # interpreted, the whole bound: the idle steps write pads
                    grid=(n_blocks // _STEP_BLOCKS if interpret else steps,),
                    in_specs=[pl.BlockSpec((bp, d), lambda t, *_: (0, 0))]
                    + [block(j) for j in range(_STEP_BLOCKS)],
                    out_specs=pl.BlockSpec(
                        (bp, _LANES),
                        lambda t, *_: (0, t * _STEP_BLOCKS // _LANES)),
                ),
                out_shape=jax.ShapeDtypeStruct((bp, n_blocks), jnp.float32),
                interpret=interpret,
                name="ivf_scan",
            )(blk_id, blk_meta, bits, total[None], q,
              *([by_column] * _STEP_BLOCKS))

        with jax.named_scope("topk.ivf.select"):
            # output tiles past the work list were never written
            best = jnp.where(step[None, :] < total, best, _PAD_SCORE)
            per_query = min(k, n_blocks)
            top, at = jax.lax.top_k(best, per_query)      # (Bp, per_query)
            chosen = blk_id[at]
            scores = pl.pallas_call(
                partial(_rescore_kernel, per_query=per_query,
                        precision=precision),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(bp * per_query,),
                    in_specs=[
                        pl.BlockSpec((bp, d), lambda i, sel: (0, 0)),
                        pl.BlockSpec((d, rows), lambda i, sel: (0, sel[i])),
                    ],
                    out_specs=pl.BlockSpec(
                        (None, 1, rows), lambda i, sel: (i, 0, 0)),
                ),
                out_shape=jax.ShapeDtypeStruct(
                    (bp * per_query, 1, rows), jnp.float32),
                interpret=interpret,
                name="ivf_rescore",
            )(chosen.reshape(-1), q, by_column)
            scores = scores.reshape(bp, per_query, rows)
            fill = blk_meta[chosen] & ((1 << _FILL_BITS) - 1)
            real = ((jnp.arange(rows)[None, None, :] < fill[:, :, None])
                    & (top[:, :, None] > _PAD_SCORE * 0.5))
            scores = jnp.where(real, scores, _PAD_SCORE).reshape(bp, -1)
            s, i = jax.lax.top_k(scores, k)
            idx = jnp.take_along_axis(chosen, i // rows, axis=1) * rows + i % rows
            # a slot that still scores at the pad floor is an empty
            # shortlist slot, not a real row: -1 for the formatter
            idx = jnp.where(s > _PAD_SCORE * 0.5, idx, -1).astype(jnp.int32)
            counts = jnp.stack(
                [jnp.broadcast_to(union_rows, (bp,)), probed_rows],
                axis=1).astype(jnp.int32)
            return jnp.concatenate([_pack_results(s, idx), counts], axis=-1)

    _SEARCH = search
    return search


_SEARCH = None


class IVFIndex:
    """Built coarse quantizer + the list layout + measured recall probe.

    Immutable after ``build`` — the owning ``DeviceFactorIndex`` swaps in
    a fresh instance on every full rebuild (the same thread that already
    refreshes the factor matrix).  The layout holds no rows of its own:
    ``build`` returns the list-ordered matrix and the owner makes it the
    resident matrix, so streaming updates to EXISTING rows need no ANN
    maintenance at all — the scatter writes a row's block and the next
    scan reads it.  A moved row keeps its list until the next rebuild;
    only structural changes (new rows) need one, and those trigger it
    anyway."""

    # queries a frame of the query program holds; the owner slices wider ones
    max_frame = _MAX_FRAME

    def __init__(self, centroids, list_blocks, list_rows, blk_meta,
                 nlist: int, nprobe: int, rows_per_block: int,
                 recall_probe: float, n_rows: int, probe_k: int):
        self.centroids = centroids      # (nlist, d) device array
        self.list_blocks = list_blocks  # (nlist,) int32: blocks a list has
        self.list_rows = list_rows      # (nlist,) int32: rows a list has
        # (n_blocks,) int32: list id << _FILL_BITS | real rows in the block
        self.blk_meta = blk_meta
        self.nlist = nlist
        self.nprobe = nprobe
        self.rows_per_block = rows_per_block
        self.recall_probe = recall_probe
        self.n_rows = n_rows
        self.probe_k = probe_k

    # -- building -----------------------------------------------------------

    @classmethod
    def default_nlist(cls, n: int) -> int:
        want = _env_int("TPUMS_ANN_NLIST", 0)
        if want > 0:
            return min(want, max(n, 1))
        return max(8, min(4096, _pow2(int(4.0 * np.sqrt(max(n, 1))))))

    @classmethod
    def default_nprobe(cls, nlist: int) -> int:
        want = _env_int("TPUMS_ANN_NPROBE", 0)
        if want > 0:
            return min(want, nlist)
        return max(4, nlist // 16)

    @classmethod
    def build(cls, rows: np.ndarray, matrix, nlist: Optional[int] = None,
              nprobe: Optional[int] = None, seed: int = 0):
        """``rows``: the catalog on the host, ``matrix``: the same rows on
        the device -> ``(index, listed, position)``: ``listed`` the
        list-ordered device matrix ``(n_blocks * rows_per_block, d)`` and
        ``position[i]`` where row ``i`` lies in it.  Phases
        ``topk.build.ann.train`` (the quantizer), ``.assign`` (every row's
        list), ``.lists`` (the layout and the reordered matrix) and
        ``.recall`` (the probe)."""
        import jax

        lloyd, assign_chunk, place, _ = _build_jits()
        dev = _target_device()
        n, d = rows.shape
        nlist = nlist or cls.default_nlist(n)
        nprobe = nprobe or cls.default_nprobe(nlist)
        rng = np.random.default_rng(seed)

        with phase("topk.build.ann.train"):
            # a bounded sample (~64 training points per centroid, capped:
            # past that, extra Lloyd work buys no recall — the probe below
            # is the arbiter, not the training-set size)
            sample_cap = min(
                n, 64 * nlist, _env_int("TPUMS_ANN_TRAIN_CAP", 1 << 17))
            picked = (np.arange(n) if sample_cap >= n
                      else np.sort(rng.choice(n, size=sample_cap, replace=False)))
            train = np.ascontiguousarray(rows[picked], dtype=np.float32)
            cent = jax.device_put(
                train[rng.choice(len(train), size=nlist, replace=False)], dev)
            train = jax.device_put(train, dev)
            for _ in range(max(_env_int("TPUMS_ANN_KMEANS_ITERS", 6), 1)):
                cent = lloyd(train, cent)
            del train

        with phase("topk.build.ann.assign"):
            chunk, starts = _whole_chunks(n, _ASSIGN_CHUNK)
            parts = [assign_chunk(matrix, cent, np.int32(s), chunk)
                     for s in starts]
            assign = np.empty((n,), np.int32)
            for s, part in zip(starts, parts):
                assign[s:s + chunk] = np.asarray(part)

        with phase("topk.build.ann.lists"):
            per = block_rows(n, nlist)
            n_blocks = block_count(n, nlist)
            counts = np.bincount(assign, minlength=nlist)
            blocks = -(-counts // per)
            first_row = (np.cumsum(blocks) - blocks) * per
            order = np.argsort(assign, kind="stable")
            listed_as = assign[order]
            position = np.empty((n,), np.int64)
            position[order] = (first_row[listed_as] + np.arange(n)
                               - (np.cumsum(counts) - counts)[listed_as])
            src = np.full((n_blocks * per,), -1, np.int64)
            src[position] = np.arange(n)
            blk_list = np.repeat(np.arange(nlist), blocks)
            nth = np.arange(len(blk_list)) - np.repeat(
                np.cumsum(blocks) - blocks, blocks)
            blk_meta = np.zeros((n_blocks,), np.int32)
            blk_meta[:len(blk_list)] = (blk_list << _FILL_BITS) | np.minimum(
                counts[blk_list] - nth * per, per)
            listed = _in_list_order(rows, src, place, dev)
            del src

        idx = cls(
            centroids=cent,
            list_blocks=jax.device_put(blocks.astype(np.int32), dev),
            list_rows=jax.device_put(counts.astype(np.int32), dev),
            blk_meta=jax.device_put(blk_meta, dev),
            nlist=nlist, nprobe=nprobe, rows_per_block=per,
            recall_probe=0.0, n_rows=n, probe_k=0,
        )
        with phase("topk.build.ann.recall"):
            idx._measure_recall(rows, matrix, listed, position, rng)
        return idx, listed, position

    def _measure_recall(self, rows: np.ndarray, matrix, listed, position,
                        rng) -> None:
        """recall@k of the tier (the probe, both kernels and the list-ordered
        layout) vs the exact tier's plain program over the row-ordered
        ``matrix``, which shares none of them: a fault in the scan, the
        select or ``position`` shows here as lost recall, as probing's loss
        does.  On a sample of catalog rows used as queries (items recommend
        their own neighborhood — the hardest realistic query distribution
        for IVF, since user vectors are smoother mixtures of the same
        factors), a frame of the batcher's widest at a time; both matrices
        are resident already, nothing of the catalog's size is put."""
        exact_frame = _build_jits()[3]
        n = self.n_rows
        nq = min(_env_int("TPUMS_ANN_PROBE_QUERIES", 64), n)
        k = min(_env_int("TPUMS_ANN_PROBE_K", 100), n)
        q = np.ascontiguousarray(
            rows[rng.choice(n, size=nq, replace=False)], dtype=np.float32)
        hits = 0
        for lo in range(0, nq, _MAX_FRAME):
            frame = _padded(q[lo:lo + _MAX_FRAME])
            got = _unpack_results(
                np.asarray(self.search(listed, frame, k))[:, :-2])[1]
            want = position[np.asarray(exact_frame(matrix, frame, k))]
            for r in range(min(_MAX_FRAME, nq - lo)):
                hits += len(np.intersect1d(want[r], got[r][got[r] >= 0]))
        self.recall_probe = hits / float(nq * k)
        self.probe_k = k

    def membership(self) -> np.ndarray:
        """``(n_blocks * rows_per_block,)`` int32: the list of the row at
        each position of the list-ordered matrix, -1 at a pad position."""
        meta = np.asarray(self.blk_meta)
        fill = meta & ((1 << _FILL_BITS) - 1)
        per = self.rows_per_block
        return np.where(np.arange(per)[None, :] < fill[:, None],
                        (meta >> _FILL_BITS)[:, None], -1).reshape(-1)

    # -- querying -----------------------------------------------------------

    def search(self, matrix, q, k: int):
        """(B, d) query frame on the host, B up to ``max_frame`` (the owner
        slices a wider one, ``topk._dispatch_frame_locked``) -> one device
        array ``(Bp, 2k + 2)`` int32, ``Bp`` = 8, 16 or 32: ``topk._pack_results``
        of (scores, idx) and two columns of counts (rows in the union of
        the frame's probed lists, the same in every row; rows scored for the
        row's query, whole blocks).  The frame is padded here with its
        first row, which probes nothing new, so one program serves every
        frame of up to 8.  ``matrix`` is the resident list-ordered matrix;
        ``idx`` are positions in it and -1 where the probed lists held
        fewer than ``k`` rows."""
        q = np.asarray(q, dtype=np.float32)
        if q.shape[0] > _MAX_FRAME:
            raise ValueError(f"an IVF frame holds up to {_MAX_FRAME} queries")
        q = _padded(q)
        return _search_jit()(
            self.centroids, self.list_blocks, self.list_rows, self.blk_meta,
            matrix, q, k=int(k), nprobe=self.nprobe,
            rows=self.rows_per_block,
            interpret=_target_device().platform != "tpu")
