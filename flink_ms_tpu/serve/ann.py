"""IVF approximate-nearest-neighbor tier for the retrieval plane.

The exact TOPK scan is linear in catalog size; past ~10M rows the scan
itself is the latency floor no matter how it is sharded.  This module
makes retrieval cost sublinear with the classic IVF (inverted-file)
recipe, adapted for maximum-inner-product retrieval over ALS item
factors:

- **Build** (off the query path, on the rebuild thread): a coarse k-means
  quantizer over the item factors — trained on-device with a jitted
  Lloyd's iteration (``segment_sum`` reduction) over a bounded training
  sample, then ONE chunked full-catalog assignment pass.  Rows land in
  fixed-capacity posting lists: a ``(nlist, list_len)`` int32 array
  padded with ``-1`` so the probe program has a single static shape
  (the same pad-to-bucket discipline as the rest of the serving plane).
- **Query**: score the query against the ``nlist`` centroids (inner
  product — the retrieval metric, not the clustering metric), take the
  ``nprobe`` best lists, gather their candidate rows FROM THE RESIDENT
  FACTOR MATRIX (the exact tier's array — the catalog exists once), and
  exactly re-rank the shortlist with a fused gather+einsum+``top_k``.
  The only approximation IVF introduces is a missing candidate; scores
  of returned items are exact by construction.
- **Contract**: the build measures recall@k against the exact scan on a
  held-out query probe and records it (``recall_probe``).  The index
  owner gates on it (``TPUMS_ANN_RECALL_MIN``, see ``topk.py``) — the
  approximation is a measured contract, not a hope.

Sizing rule of thumb (also in README):  ``nlist ~ 4*sqrt(n)`` rounded to
a power of two keeps lists ~``sqrt(n)/4`` long; ``nprobe = nlist/16``
then scans ~``n/16`` of the catalog for recall@100 in the 0.95+ range on
clustered factor geometries.  Knobs: ``TPUMS_ANN_NLIST``,
``TPUMS_ANN_NPROBE``, ``TPUMS_ANN_LIST_ALPHA`` (per-list capacity slack,
default 2x the mean occupancy — overflowing rows are dropped from the
ANN tier and show up as recall loss in the probe, never as a crash).
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional

import numpy as np

from .topk import (
    _PAD_SCORE, _SCORE_PRECISION, _pack_results, _target_device,
    _unpack_results,
)

# rows per assignment dispatch (one compiled shape).  The distance matrix
# a dispatch materializes is (chunk, nlist) f32 — 32k rows x 4096 lists is
# a bounded 512 MB peak even at the 10M-row catalog's default sizing;
# an unchunked pass would be O(n * nlist) and OOM the build.
_ASSIGN_CHUNK = 1 << 15


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def _jits():
    """The jitted programs, created on first use (keeps jax import off
    the module path — this file is imported by knob probes that never
    touch a device)."""
    global _partial_stats, _recenter, _assign, _search
    if _partial_stats is not None:
        return _partial_stats, _recenter, _assign, _search
    import jax
    import jax.numpy as jnp

    @jax.jit
    def partial_stats(x, cent):
        """One Lloyd chunk: L2-assign ``x`` to centroids and return the
        per-centroid (sum, count) partials — callers accumulate across
        chunks so the (chunk, nlist) distance matrix is the only
        catalog-scale temporary ever materialized."""
        # argmin ||x-c||^2 == argmin (||c||^2 - 2 x.c)
        d2 = jnp.sum(cent * cent, axis=1)[None, :] - 2.0 * (x @ cent.T)
        assign = jnp.argmin(d2, axis=1)
        nlist = cent.shape[0]
        sums = jax.ops.segment_sum(x, assign, num_segments=nlist)
        counts = jax.ops.segment_sum(
            jnp.ones((x.shape[0],), x.dtype), assign, num_segments=nlist
        )
        return sums, counts

    @jax.jit
    def recenter(cent, sums, counts):
        # empty clusters keep their old centroid (re-seeding would make
        # the refresh non-deterministic for no measured recall gain)
        return jnp.where(
            counts[:, None] > 0,
            sums / jnp.maximum(counts, 1.0)[:, None],
            cent,
        )

    @jax.jit
    def assign_only(x, cent):
        d2 = jnp.sum(cent * cent, axis=1)[None, :] - 2.0 * (x @ cent.T)
        return jnp.argmin(d2, axis=1).astype(jnp.int32)

    @partial(jax.jit, static_argnums=(4, 5))
    def search(cent, postings, matrix, q, k, nprobe):
        cs = q @ cent.T                        # (B, nlist) IP probe —
        _, probe = jax.lax.top_k(cs, nprobe)   # retrieval metric, not L2
        cand = postings[probe]                 # (B, nprobe, L)
        cand = cand.reshape(q.shape[0], -1)    # (B, C)
        valid = cand >= 0
        safe = jnp.where(valid, cand, 0)
        vecs = matrix[safe]                    # (B, C, d) resident gather
        scores = jnp.einsum("bcd,bd->bc", vecs, q,
                            precision=_SCORE_PRECISION)
        scores = jnp.where(valid, scores, _PAD_SCORE)
        s, i = jax.lax.top_k(scores, k)
        idx = jnp.take_along_axis(cand, i, axis=1)
        # a slot that still scores at the pad floor is an empty shortlist
        # slot, not a real row — surface it as -1 for the formatter
        idx = jnp.where(s > _PAD_SCORE * 0.5, idx, -1)
        return _pack_results(s, idx)

    _partial_stats, _recenter, _assign, _search = (
        partial_stats, recenter, assign_only, search
    )
    return _partial_stats, _recenter, _assign, _search


_partial_stats = _recenter = _assign = _search = None


class IVFIndex:
    """Built coarse quantizer + posting lists + measured recall probe.

    Immutable after ``build`` — the owning ``DeviceFactorIndex`` swaps in
    a fresh instance on every full rebuild (the same thread that already
    refreshes the factor matrix), so streaming updates to EXISTING rows
    need no ANN maintenance at all: the posting lists hold row *indices*
    and the re-rank gathers current values from the live matrix.  Only
    structural changes (new rows) stale the lists, and those trigger a
    rebuild anyway."""

    def __init__(self, centroids, postings, nlist: int, nprobe: int,
                 list_len: int, recall_probe: float, n_rows: int,
                 dropped: int, probe_k: int):
        self.centroids = centroids      # (nlist, d) device array
        self.postings = postings        # (nlist, list_len) int32 device
        self.nlist = nlist
        self.nprobe = nprobe
        self.list_len = list_len
        self.recall_probe = recall_probe
        self.n_rows = n_rows
        self.dropped = dropped          # overflow rows absent from lists
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
    def build(cls, rows: np.ndarray, nlist: Optional[int] = None,
              nprobe: Optional[int] = None, seed: int = 0) -> "IVFIndex":
        import jax

        partial_stats, recenter, assign_only, _ = _jits()
        dev = _target_device()
        rows = np.ascontiguousarray(rows, dtype=np.float32)
        n, d = rows.shape
        nlist = nlist or cls.default_nlist(n)
        nprobe = nprobe or cls.default_nprobe(nlist)
        rng = np.random.default_rng(seed)

        # -- train the quantizer on a bounded sample (~64 training points
        # per centroid, capped: past that, extra Lloyd work buys no recall
        # — the probe below is the arbiter, not the training-set size) --
        iters = _env_int("TPUMS_ANN_KMEANS_ITERS", 6)
        sample_cap = min(
            n, 64 * nlist, _env_int("TPUMS_ANN_TRAIN_CAP", 1 << 17))
        train = (
            rows if sample_cap >= n
            else rows[rng.choice(n, size=sample_cap, replace=False)]
        )
        cent = jax.device_put(
            train[rng.choice(train.shape[0], size=nlist, replace=False)],
            dev,
        )
        chunk = min(_ASSIGN_CHUNK, _pow2(max(train.shape[0], 1)))

        def chunks_of(arr):
            """Pad the tail chunk by repeating row 0 so every dispatch
            compiles at ONE (chunk, d) shape; callers slice pads off (for
            stats the pad rows are subtracted back out)."""
            for lo in range(0, arr.shape[0], chunk):
                hi = min(lo + chunk, arr.shape[0])
                block = arr[lo:hi]
                if hi - lo < chunk:
                    block = np.concatenate(
                        [block,
                         np.broadcast_to(arr[:1], (chunk - (hi - lo), d))]
                    )
                yield jax.device_put(block, dev), hi - lo

        n_tail_pad = (-train.shape[0]) % chunk
        for _ in range(max(iters, 1)):
            sums = counts = None
            for block, real in chunks_of(train):
                s, c = partial_stats(block, cent)
                sums = s if sums is None else sums + s
                counts = c if counts is None else counts + c
            if n_tail_pad:
                # the tail pad repeated row 0: remove its phantom mass
                s0, c0 = partial_stats(
                    jax.device_put(
                        np.broadcast_to(train[:1], (chunk, d)), dev),
                    cent,
                )
                sums = sums - s0 * (n_tail_pad / chunk)
                counts = counts - c0 * (n_tail_pad / chunk)
            cent = recenter(cent, sums, counts)

        # -- one full-catalog assignment pass at the same chunk shape --
        assign = np.empty((n,), np.int32)
        pos = 0
        for block, real in chunks_of(rows):
            assign[pos:pos + real] = np.asarray(
                assign_only(block, cent))[:real]
            pos += real

        # -- fixed-capacity posting lists: (nlist, L) of row indices,
        # -1-padded; rows past a list's capacity are DROPPED from the ANN
        # tier (surfaced via `dropped` and as probe recall loss) --
        alpha = float(os.environ.get("TPUMS_ANN_LIST_ALPHA", 2.0))
        list_len = max(1, int(np.ceil(alpha * n / nlist)))
        counts = np.bincount(assign, minlength=nlist)
        order = np.argsort(assign, kind="stable")
        sorted_assign = assign[order]
        starts = np.concatenate(([0], np.cumsum(counts)))[:-1]
        rank = np.arange(n) - starts[sorted_assign]
        keep = rank < list_len
        postings_np = np.full((nlist, list_len), -1, np.int32)
        postings_np[sorted_assign[keep], rank[keep]] = order[keep]
        postings = jax.device_put(postings_np, dev)
        dropped = int(n - keep.sum())

        idx = cls(
            centroids=cent, postings=postings, nlist=nlist, nprobe=nprobe,
            list_len=list_len, recall_probe=0.0, n_rows=n, dropped=dropped,
            probe_k=0,
        )
        idx._measure_recall(rows, rng)
        return idx

    def _measure_recall(self, rows: np.ndarray, rng) -> None:
        """recall@k of the probe path vs the exact scan, on a sample of
        catalog rows used as queries (items recommend their own
        neighborhood — the hardest realistic query distribution for IVF,
        since user vectors are smoother mixtures of the same factors)."""
        import jax
        import jax.numpy as jnp

        n = self.n_rows
        nq = min(_env_int("TPUMS_ANN_PROBE_QUERIES", 64), n)
        k = min(_env_int("TPUMS_ANN_PROBE_K", 100), n,
                self.nprobe * self.list_len)
        dev = _target_device()
        q = rows[rng.choice(n, size=nq, replace=False)]
        q_dev = jax.device_put(q, dev)
        mat = jax.device_put(rows, dev)
        exact = np.asarray(
            jax.jit(lambda m, x: jax.lax.top_k(
                jnp.matmul(x, m.T, precision=_SCORE_PRECISION), k)
            )(mat, q_dev)[1]
        )
        _, got = _unpack_results(np.asarray(self.search(mat, q_dev, k)))
        hits = 0
        for r in range(nq):
            hits += len(np.intersect1d(exact[r], got[r][got[r] >= 0]))
        self.recall_probe = hits / float(nq * k)
        self.probe_k = k

    def colocate(self, mesh) -> None:
        """Re-place the quantizer arrays as mesh-replicated when the
        factor matrix is mesh-sharded: jit refuses to mix a sharded
        operand with arrays committed to a single device, and the probe
        math is tiny — replicating it is free next to the row slices."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(mesh, P())
        self.centroids = jax.device_put(self.centroids, rep)
        self.postings = jax.device_put(self.postings, rep)

    # -- querying -----------------------------------------------------------

    def search(self, matrix, q, k: int):
        """(B, d) query frame -> one device array, ``topk._pack_results``
        of (scores, idx).  ``matrix`` is the resident factor matrix
        (single-device or mesh-sharded — the gather works against either
        layout); each half's width is ``min(k, nprobe*list_len)`` and
        empty shortlist slots carry ``idx == -1``."""
        search = _jits()[3]
        k_eff = min(k, self.nprobe * self.list_len)
        return search(
            self.centroids, self.postings, matrix, q, k_eff, self.nprobe
        )
