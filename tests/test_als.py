"""Blocked-ALS kernel tests: closed-form parity on tiny problems, numpy
reference half-sweeps, and multi-block == single-block equivalence on the
virtual 8-device CPU mesh (SURVEY.md §4 test strategy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ms_tpu.ops import als as A
from flink_ms_tpu.parallel.mesh import make_mesh


def _synthetic(rng, n_users=40, n_items=30, k_true=3, frac=0.6, noise=0.0):
    uf = rng.normal(size=(n_users, k_true))
    itf = rng.normal(size=(n_items, k_true))
    full = uf @ itf.T
    mask = rng.uniform(size=full.shape) < frac
    u, i = np.nonzero(mask)
    r = full[u, i] + noise * rng.normal(size=u.shape)
    return u.astype(np.int64), i.astype(np.int64), r


def _numpy_user_halfsweep(u, i, r, itf, k, lam, weighted):
    """Direct per-user normal-equation solve — the spec the kernel must match."""
    n_users = int(u.max()) + 1
    out = np.zeros((n_users, k))
    for uu in np.unique(u):
        sel = u == uu
        Y = itf[i[sel]]
        n_u = sel.sum()
        reg = lam * (n_u if weighted else 1.0)
        Amat = Y.T @ Y + reg * np.eye(k)
        out[uu] = np.linalg.solve(Amat, Y.T @ r[sel])
    return out


def _is_pad(idx, opp_per_block):
    """Entries of a (D, rows, w) bucket that name a slot of a strip."""
    return idx % opp_per_block >= opp_per_block - A._PAD_STRIP


def test_prepare_blocked_layout(rng):
    u, i, r = _synthetic(rng)
    p = A.prepare_blocked(u, i, r, 4)
    assert all(a.shape[0] == 4 for a in p.u.idx)
    # every rating accounted for exactly once (counts sum to nnz; pad
    # entries = idx pointing into the opposite side's strip)
    assert int(p.u.count.sum()) == p.nnz == len(r)
    assert int(p.i.count.sum()) == p.nnz
    assert p.i.per_block == sum(p.i.rows) + A._PAD_STRIP
    n_pads = sum(int(_is_pad(ix, p.i.per_block).sum()) for ix in p.u.idx)
    total_cells = sum(ix.size for ix in p.u.idx)
    assert total_cells - n_pads == p.nnz
    # pad entries carry zero rating
    for ix, v in zip(p.u.idx, p.u.val):
        assert (v[_is_pad(ix, p.i.per_block)] == 0).all()
    # the strip is real: never a destination for any entity's factors
    assert not _is_pad(p.i.perm, p.i.per_block).any()
    # every block ends in the strip
    assert (p.i.count[:, -A._PAD_STRIP:] == 0).all()
    # perm is a bijection into the slot space and respects block membership
    assert len(np.unique(p.u.perm)) == p.n_users
    dense_pb = -(-p.n_users // 4)
    np.testing.assert_array_equal(
        p.u.perm // p.u.per_block, np.arange(p.n_users) // dense_pb
    )
    # every bucket row's real-entry count fits its width
    for w, ix in zip(p.u.widths, p.u.idx):
        per_row = (~_is_pad(ix, p.i.per_block)).sum(axis=-1)
        assert per_row.max() <= w


def _reference_fill_side(
    row_idx, col_idx, vals, n_rows, n_blocks, side_order, opp_perm,
    opp_per_block, dtype
):
    """``_fill_side`` as it stood before the ratings were sorted as values
    and written once (its body, unchanged): an ``argsort`` of the fused key,
    three sorted copies, and a ragged fill a bucket."""
    deg, block_of, bucket_of, perm, widths, rows, per_block = side_order
    nb = len(widths)
    strip = (np.arange(n_blocks, dtype=np.int32)[:, None, None] * opp_per_block
             + (opp_per_block - A._PAD_STRIP))
    idx = [
        strip + A._strip_slots(rows[j] * widths[j]).reshape(rows[j], widths[j])
        for j in range(nb)
    ]
    val = [np.zeros((n_blocks, rows[j], widths[j]), dtype) for j in range(nb)]
    count = np.zeros((n_blocks, per_block), dtype)

    col_global = opp_perm[col_idx].astype(np.int64)
    if n_rows < (1 << 31) and col_global.size and int(col_global.max()) < (1 << 32):
        key = (row_idx.astype(np.uint64) << np.uint64(32)) | col_global.astype(
            np.uint64
        )
        order_r = np.argsort(key)
    else:  # pragma: no cover - beyond any realistic id space
        order_r = np.lexsort((col_global, row_idx))
    ent_start = np.searchsorted(row_idx[order_r], np.arange(n_rows + 1))
    col_sorted = col_global[order_r]
    val_sorted = vals[order_r]

    local = perm - block_of * per_block  # slot within block
    offsets = np.concatenate([[0], np.cumsum(rows)])
    count[(block_of, local)] = deg.astype(dtype)

    for j in range(nb):
        sel = np.nonzero(bucket_of == j)[0]  # dense entity ids in bucket j
        if len(sel) == 0:
            continue
        lens = deg[sel]
        total = int(lens.sum())
        if total == 0:
            continue
        # ragged fill: src positions into the entity-sorted rating arrays,
        # dst positions into the flattened (D*rows_j, w_j) bucket arrays
        rep = np.repeat(np.arange(len(sel)), lens)
        intra = np.arange(total) - np.repeat(
            np.concatenate([[0], np.cumsum(lens)[:-1]]), lens
        )
        src = np.repeat(ent_start[sel], lens) + intra
        flat_row = block_of[sel] * rows[j] + (local[sel] - offsets[j])
        dst = np.repeat(flat_row * widths[j], lens) + intra
        idx[j].reshape(-1)[dst] = col_sorted[src]
        val[j].reshape(-1)[dst] = val_sorted[src]
    return A.SideLayout(
        per_block=per_block,
        n_rows=n_rows,
        perm=perm,
        widths=widths,
        rows=rows,
        idx=idx,
        val=val,
        count=count,
    )


def _fill_problem(kind, rng):
    """(users, items, ratings) in no order, by the shape of the degrees;
    only ``duplicates`` rates a (user, item) pair more than once."""
    n_users, n_items = 90, 70
    if kind == "duplicates":
        u = rng.integers(0, 12, 600)
        i = rng.integers(0, 9, 600)      # 108 pairs under 600 ratings
        return u, i, np.arange(1.0, 601.0)
    if kind == "ragged":
        # skewed on both sides: degrees from one to most of the other side
        share = (rng.pareto(1.2, n_users)[:, None] + 0.02) * (
            rng.pareto(1.2, n_items)[None, :] + 0.02)
        mask = rng.uniform(size=share.shape) < np.minimum(share, 0.95)
    elif kind == "empty rung":
        # users of 1-8 ratings and of 33-40: the rungs between hold nobody
        mask = np.zeros((n_users, n_items), bool)
        for row, n in enumerate(np.where(np.arange(n_users) % 3 == 0,
                                         rng.integers(33, 41, n_users),
                                         rng.integers(1, 9, n_users))):
            mask[row, rng.choice(n_items, n, replace=False)] = True
    else:
        assert kind == "one wide"
        # one user of 900 ratings beside users of at most 8
        n_items = 1000
        mask = np.zeros((n_users, n_items), bool)
        for row, n in enumerate(rng.integers(1, 9, n_users)):
            mask[row, rng.choice(n_items, n, replace=False)] = True
        mask[17] = False
        mask[17, rng.choice(n_items, 900, replace=False)] = True
    u, i = np.nonzero(mask)
    order = rng.permutation(len(u))
    return u[order], i[order], rng.uniform(0.5, 5.0, len(u))


def _both_fills(u, i, r, blocks, dtype=np.float32, sparse=False):
    """Both sides through ``_sorted_keys`` + ``_fill_side`` and through the
    reference, from one pair of ``_side_order`` results -> [(got, want)] for
    users, items.  ``sparse``: the sides are said to be of 2^31 rows and
    2^31 slots, as a sparse id space would have them, which no 64-bit key
    holds beside the entry."""
    _, u_idx = A._dense_ids(u)
    _, i_idx = A._dense_ids(i)
    n_u, n_i = int(u_idx.max()) + 1, int(i_idx.max()) + 1
    u_order = A._side_order(u_idx, n_u, blocks, 1.5)
    i_order = A._side_order(i_idx, n_i, blocks, 1.5)
    r = np.asarray(r, np.float64)
    got, want = [], []
    for row, col, n, mine, theirs in ((u_idx, i_idx, n_u, u_order, i_order),
                                      (i_idx, u_idx, n_i, i_order, u_order)):
        keys = A._sorted_keys(row, col, theirs[3],
                              1 << 31 if sparse else n,
                              1 << 31 if sparse else blocks * theirs[6])
        assert (keys[1] is not None) == sparse   # an order: the argsort's
        got.append(A._fill_side(keys, r, n, blocks, mine, theirs[6], dtype))
        want.append(_reference_fill_side(row, col, r, n, blocks, mine,
                                         theirs[3], theirs[6], dtype))
    return list(zip(got, want))


@pytest.mark.parametrize("blocks", [1, 2, 4])
@pytest.mark.parametrize("sparse", [False, True],
                         ids=["value sort", "argsort"])
@pytest.mark.parametrize("kind", ["ragged", "empty rung", "one wide"])
def test_the_fill_is_the_one_it_replaces(rng, monkeypatch, kind, sparse,
                                         blocks):
    """Sorted as values (row, slot and entry fit the key) or through
    ``argsort`` of the fused key (they do not), written once into one
    buffer a side, some tens of entries a step: every array of both sides
    is the bucket-by-bucket fill's, dtype included."""
    monkeypatch.setattr(A, "_FILL_STEP", 37)   # lists straddle the steps
    u, i, r = _fill_problem(kind, rng)
    for side, (got, want) in enumerate(_both_fills(u, i, r, blocks,
                                                   sparse=sparse)):
        assert (got.per_block, got.n_rows, got.widths, got.rows) == (
            want.per_block, want.n_rows, want.widths, want.rows)
        if kind != "ragged" and side == 0:
            # the users' ladder (ratio 1.5) has lost the rungs nobody is on
            assert max(np.divide(got.widths[:-1], got.widths[1:])) > 2
        for name in ("perm", "count"):
            a, b = getattr(got, name), getattr(want, name)
            assert a.dtype == b.dtype and np.array_equal(a, b), name
        for name in ("idx", "val"):
            mine, theirs = getattr(got, name), getattr(want, name)
            assert len(mine) == len(theirs) == len(want.widths)
            for a, b in zip(mine, theirs):
                assert a.dtype == b.dtype and a.shape == b.shape, name
                assert np.array_equal(a, b), name
                assert a.flags.c_contiguous
        # one buffer a side: a bucket's array is a slice of it
        assert len({id(a.base.base) for a in got.idx}) == 1


@pytest.mark.parametrize("blocks", [1, 2, 4])
def test_ratings_of_one_pair_keep_the_inputs_order(rng, monkeypatch, blocks):
    """Two ratings of one (user, item) pair have one (row, slot): ``idx`` is
    the reference's, a list holds the same ratings at each slot, and where
    the reference's unstable sort leaves them in either order the value sort
    leaves them in the input's (ratings here ascend with the input)."""
    monkeypatch.setattr(A, "_FILL_STEP", 37)
    u, i, r = _fill_problem("duplicates", rng)
    repeated = 0
    for got, want in _both_fills(u, i, r, blocks):
        for a, b, x, y in zip(got.idx, want.idx, got.val, want.val):
            assert np.array_equal(a, b)
            # (slot, rating) pairs of a list, as a multiset
            assert np.array_equal(np.sort(a * 1000.0 + x, axis=-1),
                                  np.sort(b * 1000.0 + y, axis=-1))
            same_slot = (a[..., 1:] == a[..., :-1]) & (x[..., 1:] > 0)
            assert (x[..., 1:] > x[..., :-1])[same_slot].all()
            repeated += int(same_slot.sum())
    assert repeated > 500   # both sides: most of the 600 repeat a pair


@pytest.mark.parametrize("blocks", [1, 4])
@pytest.mark.parametrize("strip", [1, 3, 8, None])
def test_pads_are_spread_over_the_opposite_strip(rng, monkeypatch, strip,
                                                 blocks):
    """Whatever the strip's size (None = the module's): every pad position
    of block d names a slot of the opposite side's block d strip, no slot
    twice within a strip's length of consecutive flat positions, a list's
    real entries come first and ascend, and the routed exchange's unused
    send slots take the strip the same way."""
    if strip is not None:
        monkeypatch.setattr(A, "_PAD_STRIP", strip)
    P = A._PAD_STRIP
    u = np.repeat(np.arange(120), rng.integers(1, 60, 120))
    i = rng.integers(0, 70, len(u))
    p = A.prepare_blocked(u, i, rng.uniform(1, 5, len(u)), blocks)
    for side, opp in ((p.u, p.i), (p.i, p.u)):
        assert side.per_block == sum(side.rows) + P
        assert (side.count[:, -P:] == 0).all()
        assert not _is_pad(side.perm, side.per_block).any()
        seen_pads = 0
        for ix, val in zip(side.idx, side.val):
            pad = _is_pad(ix, opp.per_block)
            seen_pads += int(pad.sum())
            assert (val[pad] == 0).all() and (val[~pad] != 0).all()
            for d in range(blocks):
                flat, flat_pad = ix[d].ravel(), pad[d].ravel()
                # a pad names its own block's strip, by its flat position
                where = np.nonzero(flat_pad)[0]
                np.testing.assert_array_equal(
                    flat[where], (d + 1) * opp.per_block - P + where % P)
                # ... so equal slots are a multiple of P positions apart
                for slot in np.unique(flat[where]):
                    at = where[flat[where] == slot]
                    assert (np.diff(at) >= P).all()
                # real entries lead every list, in ascending address order
                for row, row_pad in zip(ix[d], pad[d]):
                    n_real = int((~row_pad).sum())
                    assert not row_pad[:n_real].any() and row_pad[n_real:].all()
                    assert (np.diff(row[:n_real]) >= 0).all()
        assert seen_pads == sum(ix.size for ix in side.idx) - p.nnz > 0
    if blocks > 1:
        # pads are the block's own shard's rows and no entity lives on a
        # strip, so nothing of a strip is needed from another block: a
        # route is real rows, then the strip spread over what is left
        routed = A.build_routing(p.u, p.i, blocks)
        lo = p.i.per_block - P
        tail = lo + np.arange(routed.r_max) % P
        for s in range(blocks):
            np.testing.assert_array_equal(routed.send_idx[s, s], tail)
            for d in range(blocks):
                n_real = int((routed.send_idx[s, d] < lo).sum())
                assert (routed.send_idx[s, d, :n_real] < lo).all()
                np.testing.assert_array_equal(
                    routed.send_idx[s, d, n_real:], tail[n_real:])


def _one_slot_layout(problem):
    """The layout before the strip: every pad of every block names ONE
    slot, the last of the opposite side's block 0.  Same shapes, same real
    entries in the same places; only where the pads point differs."""
    import dataclasses

    def aimed(side, opp):
        idx = [np.where(_is_pad(ix, opp.per_block),
                        np.int32(opp.per_block - 1), ix) for ix in side.idx]
        return dataclasses.replace(side, idx=idx)

    return dataclasses.replace(
        problem, u=aimed(problem.u, problem.i), i=aimed(problem.i, problem.u),
        routing={})


def _kernel(platform, y_dtype, dtype, k, precision="highest"):
    return "kernel"


@pytest.mark.parametrize("blocks, exchange", [
    (1, "auto"), (4, "gather"), (4, "routed")])
@pytest.mark.parametrize("route", ["materialised", "per chunk"])
@pytest.mark.parametrize("mode, assembly", [
    ("explicit", "einsum"), ("explicit", "kernel"), ("implicit", "einsum"),
    ("implicit", "kernel")])
def test_the_strip_stays_zero_and_moves_no_factor(rng, monkeypatch, mode,
                                                  assembly, route, blocks,
                                                  exchange):
    """Two iterations on the strip layout against the same problem with
    every pad aimed at one slot, as before PR 34: the same terms in the
    same order, so real entities' factors agree to the last bit; the
    strip's rows are exact zeros at the start and after the iterations on
    both sides; the gauge counts the strips."""
    from flink_ms_tpu.obs import metrics as obs_metrics

    monkeypatch.setenv("FLINK_MS_ALS_FUSED", "1" if route == "per chunk" else "0")
    monkeypatch.setenv("FLINK_MS_ALS_EXCHANGE_MODE", exchange)
    # small enough that the widest buckets run under lax.map
    monkeypatch.setenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES", "4096")
    if assembly == "kernel":
        # the chip's kernel interpreted, handing A to the Pallas solver
        monkeypatch.setenv("FLINK_MS_ALS_SOLVER", "pallas")
        monkeypatch.setattr(A, "resolve_assembly", _kernel)
    A._SWEEP_CACHE.clear()   # the resolver is not in the sweep's cache key
    P, k = A._PAD_STRIP, 4
    u = np.repeat(np.arange(90), rng.integers(1, 40, 90))
    i = rng.integers(0, 50, len(u))
    r = np.abs(rng.normal(size=len(u))) + 0.5
    problem = A.prepare_blocked(u, i, r, blocks)
    init = (rng.normal(size=(problem.n_users, k)).astype(np.float32),
            rng.normal(size=(problem.n_items, k)).astype(np.float32))
    cfg = A.ALSConfig(num_factors=k, iterations=2, lambda_=0.1,
                      implicit=mode == "implicit", alpha=10.0,
                      exchange_dtype=None)
    mesh = make_mesh(blocks)
    states = []
    try:
        for layout in (problem, _one_slot_layout(problem)):
            fit_fn, dev_args = A.compile_fit(layout, cfg, mesh, init=init)
            if layout is problem:
                if exchange == "routed":
                    assert A._exchange_plan(layout, blocks)["u"] is not None
                gauges = {g["name"]: g["value"] for g in
                          obs_metrics.get_registry().snapshot()["gauges"]
                          if not g["labels"]}
                assert gauges["tpums_als_pad_slots"] == 2 * blocks * P
                for table in dev_args[:2]:
                    assert (np.asarray(table)[:, -P:] == 0).all()
            states.append([np.asarray(t) for t in
                           fit_fn(jnp.asarray(2, jnp.int32), *dev_args)])
    finally:
        A._SWEEP_CACHE.clear()
    (uf, itf), (uf_old, itf_old) = states
    for table, old, side in ((uf, uf_old, problem.u), (itf, itf_old, problem.i)):
        assert table.shape == (blocks, side.per_block, k)
        assert (table[:, -P:] == 0).all()
        got = table.reshape(-1, k)[side.perm]
        assert np.abs(got).min(axis=1).max() > 0  # the iterations ran
        np.testing.assert_array_equal(got, old.reshape(-1, k)[side.perm])


# -- a gather table read through segments that fit the fast memory ------------

def _fast_memory_for(rows, k, segments):
    """A fast memory beside which a table of `rows` slots of k f32 values
    is read in `segments` segments (`table_segments`' own arithmetic)."""
    from flink_ms_tpu.ops.assemble_pallas import _lanes_vmem_limit

    fit = -(-(rows - A._PAD_STRIP) // segments) + A._PAD_STRIP
    return _lanes_vmem_limit(k) + A._FAST_MEMORY_SLACK + fit * 512


def _ratings_problem(rng, n_users=300, n_items=90, nnz=6000, lonely=10):
    """Every id present; the last `lonely` items hold one rating each, so
    their lists have no entry in all segments but one."""
    nnz -= lonely
    users = np.concatenate([np.arange(n_users),
                            rng.integers(0, n_users, nnz - n_users)])
    items = np.concatenate([np.arange(n_items),
                            rng.integers(0, n_items, nnz - n_items)])
    rng.shuffle(items)
    users = np.concatenate([users, rng.integers(0, n_users, lonely)])
    items = np.concatenate([items, n_items + np.arange(lonely)])
    return users, items, rng.uniform(1, 5, len(users))


@pytest.mark.parametrize("rows, k, fast, reserved, want", [
    # the three ALS cells on a v5e (128 MiB; the kernel's limit 40 MB up to
    # rank 64, 49 MiB at 100): both of als-ml20m's tables, msd-ials' songs
    # and netflix' movies lie whole, the two user tables in four segments
    (138621, 50, 128 << 20, 40 << 20, 1),
    (26872, 50, 128 << 20, 40 << 20, 1),
    (41268, 64, 128 << 20, 40 << 20, 1),
    (571483, 64, 128 << 20, 40 << 20, 4),
    (17898, 100, 128 << 20, 49 << 20, 1),
    (480317, 100, 128 << 20, 49 << 20, 4),
    # rank 129 takes two lane tiles a row
    (100000, 129, 128 << 20, 61 << 20, 2),
    # no fast memory reported (the CPU): the table is read whole
    (571483, 64, None, 40 << 20, 1),
    # a budget under one row still gives segments of a row
    (1128, 8, 1, 0, 1000),
])
def test_segments_are_a_function_of_the_sizes(rows, k, fast, reserved, want):
    assert A.table_segments(rows, k, 4, fast, reserved) == want
    if fast and want > 1:
        # the fewest: one segment fewer does not fit beside the kernel
        seg = -(-(rows - A._PAD_STRIP) // (want - 1)) + A._PAD_STRIP
        lane_bytes = -(-k // 128) * 512
        assert seg * lane_bytes > fast - reserved - A._FAST_MEMORY_SLACK


@pytest.mark.parametrize("w, share, longest, want", [
    # netflix-als-f100's widest movie bucket in the heaviest users' segment:
    # 327,712 * 0.719 + 6 sd (1,544), where the longest run found was 167,536
    (327712, 0.719, 167536, 237176),
    # the expectation governs: the width does not move with the longest run
    (327712, 0.719, 237000, 237176),
    # ... until the data are not paired at random: then the run does
    (327712, 0.719, 250001, 250008),
    (1120, 0.027, 56, 64),         # 30.2 + 6 * 5.4 = 62.8
    (216, 0.085, 40, 48),
    (24, 0.5, 24, 24),             # never wider than the bucket
    (8, 0.0, 0, 8),                # an empty segment still has a piece
])
def test_a_pieces_width_comes_from_the_degrees_where_pairing_is_random(
        w, share, longest, want):
    assert A._piece_width(w, share, longest) == want


@pytest.mark.parametrize("segments", [2, 3, 5])
def test_cut_lists_keep_every_rating_in_order_and_pad_on_their_own_strip(
        rng, segments):
    """Each bucket's S pieces: a list's real entries lead and ascend, lie
    inside the segment, and read together in segment order are the list as
    `_fill_side` stored it (no rating dropped, moved or reordered); a
    piece is as wide as `_piece_width` says and never narrower than its
    longest run; position p of a piece's flat order pads on slot p mod
    _PAD_STRIP of the segment's own strip, so no slot comes back within
    _PAD_STRIP positions."""
    P = A._PAD_STRIP
    users, items, ratings = _ratings_problem(rng)
    p = A.prepare_blocked(users, items, ratings, 1)
    empty_runs = 0
    for side, opp in ((p.i, p.u), (p.u, p.i)):
        real = opp.per_block - P
        cut = A.cut_side(side, opp, segments)
        assert cut.segments == segments
        assert cut.seg_rows == -(-real // segments)
        held = [opp.count[0, lo:min(lo + cut.seg_rows, real)].sum()
                for lo in range(0, real, cut.seg_rows)]
        assert sum(held) == p.nnz
        for j, (ix, vl) in enumerate(zip(side.idx, side.val)):
            assert len(cut.idx[j]) == len(cut.val[j]) == segments
            lists = [[] for _ in range(side.rows[j])]
            for s in range(segments):
                piece, rating = cut.idx[j][s][0], cut.val[j][s][0]
                assert piece.dtype == np.int32
                assert piece.shape == rating.shape == (side.rows[j],
                                                       cut.widths[j][s])
                pad = piece >= cut.seg_rows
                runs = (~pad).sum(axis=1)
                empty_runs += int((runs == 0).sum())
                assert runs.max() <= piece.shape[1] == A._piece_width(
                    side.widths[j], held[s] / p.nnz, int(runs.max()))
                where = np.nonzero(pad.ravel())[0]
                np.testing.assert_array_equal(
                    piece.ravel()[where], cut.seg_rows + where % P)
                assert (rating[pad] == 0).all() and (rating[~pad] != 0).all()
                for row, (slots, vals, n) in enumerate(zip(piece, rating, runs)):
                    assert not pad[row, :n].any() and pad[row, n:].all()
                    assert (np.diff(slots[:n]) >= 0).all()
                    assert (slots[:n] + s * cut.seg_rows < real).all()
                    lists[row].append((slots[:n] + s * cut.seg_rows, vals[:n]))
            for row, parts in enumerate(lists):
                whole = ix[0][row] < real
                np.testing.assert_array_equal(
                    np.concatenate([a for a, _ in parts]), ix[0][row][whole])
                np.testing.assert_array_equal(
                    np.concatenate([v for _, v in parts]), vl[0][row][whole])
    assert empty_runs > 0   # some list has no entry in some segment


@pytest.mark.parametrize("segments", [2, 3])
@pytest.mark.parametrize("route", ["materialised", "per chunk"])
@pytest.mark.parametrize("assembly", ["einsum", "kernel"])
def test_a_table_read_in_segments_gives_the_whole_tables_fit(
        rng, monkeypatch, assembly, route, segments):
    """Explicit mode, two iterations: with a fast memory that holds the
    user table in `segments` pieces (and the smaller item table in fewer),
    both halves' sums are the whole table's in another order."""
    monkeypatch.setenv("FLINK_MS_ALS_FUSED", "1" if route == "per chunk" else "0")
    # small enough that the widest pieces run under lax.map
    monkeypatch.setenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES",
                       "65536" if assembly == "kernel" else "8192")
    if assembly == "kernel":
        monkeypatch.setenv("FLINK_MS_ALS_SOLVER", "pallas")
        monkeypatch.setattr(A, "resolve_assembly", _kernel)
    k = 8
    users, items, ratings = _ratings_problem(rng)
    init = (rng.random((300, k), dtype=np.float32),
            rng.random((100, k), dtype=np.float32))
    cfg = A.ALSConfig(num_factors=k, iterations=2, lambda_=0.1,
                      exchange_dtype=None)
    mesh = make_mesh(1)
    problem = A.prepare_blocked(users, items, ratings, 1)
    fast = _fast_memory_for(problem.i.per_block, k, segments)
    fits = []
    try:
        for memory in (None, fast):
            monkeypatch.setattr(A, "fast_memory", lambda device: memory)
            A._SWEEP_CACHE.clear()   # the resolver is not in its key
            fits.append(A.als_fit(users, items, ratings, cfg, mesh,
                                  problem=problem, init=init))
            want = {"u": segments if memory else 1,
                    "i": A.table_segments(problem.u.per_block, k, 4, memory,
                                          fast - 128 * 512) if memory else 1}
            assert A._segments(problem, cfg, mesh)["u"] == want["u"]
    finally:
        A._SWEEP_CACHE.clear()
    whole, cut = fits
    assert A._segments(problem, cfg, mesh)["i"] > segments
    for got, ref in ((cut.user_factors, whole.user_factors),
                     (cut.item_factors, whole.item_factors)):
        assert np.abs(got - ref).max() / np.abs(ref).max() < 2e-5


def test_with_no_fast_memory_the_sweep_is_the_parents_to_the_character():
    """The CPU reports no fast memory: S = 1 on both sides and the jaxpr of
    `fit_body` on als-ml20m's tiny twin is the one the commit before the
    segments traced (its digest, taken from that commit's tree)."""
    import hashlib
    import json
    import os

    from benchmark import synth

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark/tests/tiny/als-tiny.json")) as f:
        cell = json.load(f)
    users, items, values, _ = synth.als_problem(cell, 1)
    problem = A.prepare_blocked(users, items, values, 1)
    cfg = A.ALSConfig(num_factors=cell["rank"], iterations=1,
                      lambda_=cell["lambda"], dtype=jnp.float32,
                      assembly_precision=cell["assembly_precision"],
                      exchange_dtype=cell["exchange_dtype"])
    mesh = make_mesh(1)
    assert A._segments(problem, cfg, mesh) == {"u": 1, "i": 1}
    assert A._cuts(problem, cfg, mesh) == {"u": None, "i": None}
    fit_fn, dev_args = A.compile_fit(problem, cfg, mesh)
    text = str(jax.make_jaxpr(lambda n, *a: fit_fn(n, *a))(
        jnp.asarray(1, jnp.int32), *dev_args))
    assert hashlib.sha1(text.encode()).hexdigest() == (
        "016e8c13c90951ad32f65924e177579f0d11b732")


@pytest.mark.parametrize("blocks, platform, assembly, want", [
    (1, "cpu", "einsum", True),     # a test's fast memory cuts a CPU fit
    (4, "cpu", "einsum", False),    # a mesh of several devices reads whole
    (1, "tpu", "kernel", True),     # the cells' path
    (1, "tpu", "einsum", False),    # a TPU's einsum pair (bf16 exchange)
])
def test_which_fits_read_their_tables_in_segments(rng, monkeypatch, blocks,
                                                  platform, assembly, want):
    """D > 1 holds S = 1 (the gathered table of D blocks is not cut), and
    so does the einsum pair on a TPU; a fit over four devices with a fast
    memory reported is the fit without one."""
    import types

    k = 8
    users, items, ratings = _ratings_problem(rng)
    problem = A.prepare_blocked(users, items, ratings, blocks)
    cfg = A.ALSConfig(num_factors=k, iterations=1, exchange_dtype=None)
    init = (rng.random((300, k), dtype=np.float32),
            rng.random((100, k), dtype=np.float32))
    if blocks > 1:
        whole = A.als_fit(users, items, ratings, cfg, make_mesh(blocks),
                          problem=problem, init=init)
    fast = _fast_memory_for(problem.i.per_block, k, 3)   # the smaller
    monkeypatch.setattr(A, "fast_memory", lambda device: fast)
    A._SWEEP_CACHE.clear()
    try:
        if blocks > 1:
            same = A.als_fit(users, items, ratings, cfg, make_mesh(blocks),
                             problem=problem, init=init)
            assert not problem.cuts
            np.testing.assert_array_equal(same.user_factors,
                                          whole.user_factors)
            np.testing.assert_array_equal(same.item_factors,
                                          whole.item_factors)
    finally:
        A._SWEEP_CACHE.clear()
    monkeypatch.setattr(A, "resolve_assembly", lambda *a, **kw: assembly)
    device = types.SimpleNamespace(platform=platform)
    mesh = types.SimpleNamespace(
        devices=np.array([device] * blocks), shape={A.BLOCK_AXIS: blocks})
    got = A._segments(problem, cfg, mesh)
    assert (got["i"] > 1) is want and (got["u"] > 1) is want


def test_the_gauges_and_the_line_say_what_was_cut(rng, monkeypatch, capsys):
    """`tpums_als_table_segments{kind}`, `tpums_als_segmented_entries`
    beside `tpums_als_entries` (a cut side's pieces counted as gathered,
    pads and all), the phase around the cut, and the words of the
    `[als] assembly:` line."""
    from flink_ms_tpu.obs import metrics as obs_metrics
    from flink_ms_tpu.obs import tracing

    k = 8
    users, items, ratings = _ratings_problem(rng)
    problem = A.prepare_blocked(users, items, ratings, 1)
    cfg = A.ALSConfig(num_factors=k, iterations=1, exchange_dtype=None)
    mesh = make_mesh(1)

    def gauges():
        return {(g["name"], g["labels"].get("kind")): g["value"]
                for g in obs_metrics.get_registry().snapshot()["gauges"]
                if g["name"].startswith("tpums_als_")}

    A.compile_fit(problem, cfg, mesh)
    whole = gauges()
    assert whole["tpums_als_segmented_entries", None] == 0
    assert whole["tpums_als_table_segments", "u"] == 1
    assert whole["tpums_als_table_segments", "i"] == 1
    # the user table (the item half's) in three segments, the item table whole
    item_rows = problem.i.per_block
    fast = _fast_memory_for(problem.u.per_block, k, 3)
    assert A.table_segments(item_rows, k, 4, fast, fast - 512 * (
        -(-(problem.u.per_block - 128) // 3) + 128) - A._FAST_MEMORY_SLACK) == 1
    monkeypatch.setattr(A, "fast_memory", lambda device: fast)
    before = len([p for p in tracing.phase_log()
                  if p["name"] == "als.prepare.segment"])
    A._SWEEP_CACHE.clear()
    try:
        A.compile_fit(problem, cfg, mesh)
        A.compile_fit(problem, cfg, mesh)   # the cut is kept on the problem
    finally:
        A._SWEEP_CACHE.clear()
    assert len([p for p in tracing.phase_log()
                if p["name"] == "als.prepare.segment"]) == before + 1
    got = gauges()
    cut = problem.cuts["i", 3]
    pieces = sum(a.size for bucket in cut.idx for a in bucket)
    assert got["tpums_als_table_segments", "u"] == 1
    assert got["tpums_als_table_segments", "i"] == 3
    assert got["tpums_als_segmented_entries", None] == pieces
    assert got["tpums_als_entries", None] == pieces + sum(
        a.size for a in problem.u.idx)
    assert got["tpums_als_pad_entries", None] == (
        got["tpums_als_entries", None] - 2 * len(ratings))
    assert got["tpums_als_entries", None] > whole["tpums_als_entries", None]
    A._log_assembly(problem, "kernel", True, k, {"u": False, "i": False},
                    cuts={"u": None, "i": cut}, exchange="float32")
    u, i = capsys.readouterr().out.split("i-sweep")
    assert f"table in 1 segment of {item_rows} rows" in u
    assert f"table in 3 segments of {cut.seg_rows} rows" in i
    assert f"of {pieces} padded ratings" in i


def test_assembly_matches_numpy(rng):
    u, i, r = _synthetic(rng, n_users=12, n_items=9)
    k = 4
    p = A.prepare_blocked(u, i, r, 1)
    itf = rng.normal(size=(9, k)).astype(np.float32)
    y_all = np.zeros((p.i.per_block, k), dtype=np.float32)
    y_all[p.i.perm] = itf  # factor table lives in slot order
    buckets = [
        (jnp.asarray(p.u.idx[j][0]), jnp.asarray(p.u.val[j][0]))
        for j in range(len(p.u.widths))
    ]
    Amat, b = A._assemble_normal_eqs(
        jnp.asarray(y_all), buckets, False, 40.0, jnp.float32
    )
    Amat, b = np.asarray(Amat), np.asarray(b)
    for uu in range(12):
        sel = u == uu
        Y = itf[i[sel]]
        slot = p.u.perm[uu]
        np.testing.assert_allclose(Amat[slot], Y.T @ Y, rtol=1e-4)
        np.testing.assert_allclose(b[slot], Y.T @ r[sel], rtol=1e-4)


@pytest.mark.parametrize("k", [3, 8, 16, 50])
@pytest.mark.parametrize("solver", ["lax", "pallas"])
def test_chol_solve_matches_numpy(rng, monkeypatch, solver, k):
    """Both solvers through the seam a sweep calls, selected by the knob:
    on the CPU ``pallas`` is the chip's kernel interpreted."""
    monkeypatch.setenv("FLINK_MS_ALS_SOLVER", solver)
    n = 257
    G = rng.standard_normal((n, k, k)).astype(np.float32)
    A_ = G @ G.transpose(0, 2, 1) + 5.0 * np.eye(k, dtype=np.float32)
    b = rng.standard_normal((n, k)).astype(np.float32)
    x = np.asarray(
        jax.jit(lambda a, c: A._chol_solve(a, c, "cpu"))(
            jnp.asarray(A_), jnp.asarray(b))
    )
    x_ref = np.linalg.solve(
        A_.astype(np.float64), b.astype(np.float64)[..., None]
    )[..., 0]
    np.testing.assert_allclose(x, x_ref, rtol=2e-3, atol=2e-4)


def test_predict_chunked_equals_unchunked(rng, monkeypatch):
    """Chunked prediction (padded-tail fixed-shape device calls) is
    element-equal to the single-call path — the chunking exists because an
    unchunked 20M-pair predict OOM'd 16 GB HBM."""
    m = A.ALSModel(
        user_ids=np.arange(80), item_ids=np.arange(50),
        user_factors=rng.normal(size=(80, 6)).astype(np.float32),
        item_factors=rng.normal(size=(50, 6)).astype(np.float32),
    )
    u = rng.integers(0, 90, 30000)  # incl. some unknown ids -> score 0
    i = rng.integers(0, 55, 30000)
    full = A.predict(m, u, i)
    monkeypatch.setenv("FLINK_MS_PREDICT_CHUNK", "4097")
    np.testing.assert_array_equal(A.predict(m, u, i), full)


def test_auto_solver_resolution(monkeypatch):
    """"auto" resolves per platform: the Pallas kernel on a TPU, LAPACK-
    backed lax everywhere else; a solver named by the knob passes through."""
    monkeypatch.delenv("FLINK_MS_ALS_SOLVER", raising=False)
    assert A.resolve_solver("tpu") == "pallas"
    assert A.resolve_solver("cpu") == "lax"
    assert A.resolve_solver(None) == "lax"
    monkeypatch.setenv("FLINK_MS_ALS_SOLVER", "auto")
    assert A.resolve_solver("tpu") == "pallas"
    monkeypatch.setenv("FLINK_MS_ALS_SOLVER", "lax")
    assert A.resolve_solver("tpu") == "lax"
    monkeypatch.setenv("FLINK_MS_ALS_SOLVER", "pallas")
    assert A.resolve_solver("cpu") == "pallas"


@pytest.mark.parametrize("value", ["banana", "panel", "unrolled", ""])
def test_unknown_solver_value_raises(rng, monkeypatch, value):
    """A name that is no solver stops the fit before anything is traced,
    with the three that are in the message."""
    monkeypatch.setenv("FLINK_MS_ALS_SOLVER", value)
    with pytest.raises(ValueError, match=r"auto \| pallas \| lax"):
        A.resolve_solver("cpu")
    u, i, r = _synthetic(rng, n_users=12, n_items=9)
    traced = []
    monkeypatch.setattr(A, "_make_sweep",
                        lambda *a, **kw: traced.append(1))
    with pytest.raises(ValueError, match="FLINK_MS_ALS_SOLVER"):
        A.als_fit(u, i, r, A.ALSConfig(num_factors=3, iterations=1),
                  make_mesh(1))
    assert not traced


def test_auto_exchange_resolution():
    """exchange_dtype="auto" resolves per backend — bfloat16 on TPU
    (chip-measured +20% at +1.4e-5 relative RMSE delta), full precision
    elsewhere; explicit values and None pass through untouched."""
    assert A.resolve_exchange("auto", "tpu") == "bfloat16"
    assert A.resolve_exchange("auto", "cpu") is None
    assert A.resolve_exchange("auto", None) is None
    assert A.resolve_exchange(None, "tpu") is None
    assert A.resolve_exchange("bfloat16", "cpu") == "bfloat16"
    assert A.ALSConfig().exchange_dtype == "auto"


@pytest.mark.parametrize("weighted", [True, False])
def test_one_iteration_matches_numpy(rng, weighted):
    u, i, r = _synthetic(rng, n_users=15, n_items=11)
    k, lam = 4, 0.3
    uf0 = rng.normal(size=(15, k)).astype(np.float32)
    itf0 = rng.normal(size=(11, k)).astype(np.float32)
    mesh = make_mesh(1)
    cfg = A.ALSConfig(num_factors=k, iterations=1, lambda_=lam, weighted_reg=weighted)
    model = A.als_fit(u, i, r, cfg, mesh, init=(uf0, itf0))

    uf_expect = _numpy_user_halfsweep(u, i, r, itf0, k, lam, weighted)
    np.testing.assert_allclose(model.user_factors, uf_expect, rtol=2e-3, atol=2e-4)
    itf_expect = _numpy_user_halfsweep(i, u, r, uf_expect, k, lam, weighted)
    np.testing.assert_allclose(model.item_factors, itf_expect, rtol=2e-3, atol=2e-4)


def test_multiblock_equals_singleblock(rng):
    u, i, r = _synthetic(rng, n_users=50, n_items=37)
    k = 5
    uf0 = rng.normal(size=(50, k)).astype(np.float32)
    itf0 = rng.normal(size=(37, k)).astype(np.float32)
    cfg = A.ALSConfig(num_factors=k, iterations=3, lambda_=0.1)
    m1 = A.als_fit(u, i, r, cfg, make_mesh(1), init=(uf0, itf0))
    m8 = A.als_fit(u, i, r, cfg, make_mesh(8), init=(uf0, itf0))
    np.testing.assert_allclose(
        m1.user_factors, m8.user_factors, rtol=5e-2, atol=5e-3
    )
    np.testing.assert_allclose(
        m1.item_factors, m8.item_factors, rtol=5e-2, atol=5e-3
    )


def test_dense_ids_matches_unique(rng):
    """Bitmap fast path == np.unique on every id regime it claims."""
    for arr in (
        rng.integers(0, 50, 500),                      # dense small ints
        rng.integers(0, 10**6, 300),                   # sparse, under the
        #                            1<<20 bitmap floor: still fast path
        np.array([5, 5_000_000, 5, 7]),                # huge gap (fallback:
        #                            mx > max(4n, 1<<20))
        np.array([-3, 7, 7, 0]),                       # negative (fallback)
        rng.uniform(0, 9, 100).round(1),               # floats (fallback)
    ):
        ids, inv = A._dense_ids(np.asarray(arr))
        ids_ref, inv_ref = np.unique(np.asarray(arr), return_inverse=True)
        np.testing.assert_array_equal(ids, ids_ref)
        np.testing.assert_array_equal(inv, inv_ref)


def test_chunked_assembly_matches_unchunked(rng, monkeypatch):
    """A tiny FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES forces the lax.map chunked
    path; factors must match the single-shot assembly (same math on the
    same rows — tolerance only covers codegen-level rounding)."""
    u, i, r = _synthetic(rng, n_users=30, n_items=20)
    k = 4
    uf0 = rng.normal(size=(30, k)).astype(np.float32)
    itf0 = rng.normal(size=(20, k)).astype(np.float32)
    cfg = A.ALSConfig(num_factors=k, iterations=2, lambda_=0.1)
    mesh = make_mesh(2)
    plain = A.als_fit(u, i, r, cfg, mesh, init=(uf0, itf0))
    monkeypatch.setenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES", "512")
    chunked = A.als_fit(u, i, r, cfg, mesh, init=(uf0, itf0))
    np.testing.assert_allclose(
        chunked.user_factors, plain.user_factors, rtol=1e-3, atol=1e-5
    )
    np.testing.assert_allclose(
        chunked.item_factors, plain.item_factors, rtol=1e-3, atol=1e-5
    )


def test_fused_solve_matches_unfused(rng, monkeypatch):
    """FLINK_MS_ALS_FUSED=1 solves each bucket straight out of its
    assembly chunks (the (per_block, k, k) tensor never materializes);
    multi-block factors must match the unfused path — chunking is over
    the batch row axis only, so the per-row arithmetic is identical."""
    u, i, r = _synthetic(rng, n_users=60, n_items=45, k_true=3, noise=0.05)
    k = 5
    uf0 = rng.normal(size=(60, k)).astype(np.float32)
    itf0 = rng.normal(size=(45, k)).astype(np.float32)
    cfg = A.ALSConfig(num_factors=k, iterations=3, lambda_=0.1)
    mesh = make_mesh()
    plain = A.als_fit(u, i, r, cfg, mesh, init=(uf0, itf0))
    monkeypatch.setenv("FLINK_MS_ALS_FUSED", "1")
    fused = A.als_fit(u, i, r, cfg, mesh, init=(uf0, itf0))
    np.testing.assert_allclose(
        fused.user_factors, plain.user_factors, rtol=1e-4, atol=1e-6
    )
    np.testing.assert_allclose(
        fused.item_factors, plain.item_factors, rtol=1e-4, atol=1e-6
    )
    # fused + forced lax.map chunking (the scale-envelope configuration)
    monkeypatch.setenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES", "2048")
    fused_c = A.als_fit(u, i, r, cfg, mesh, init=(uf0, itf0))
    np.testing.assert_allclose(
        fused_c.user_factors, plain.user_factors, rtol=1e-4, atol=1e-6
    )
    # fused + chunked + the pallas solver (interpret off-TPU): the exact
    # combination whose lane-major relayout OOM'd on chip — the scan body
    # must trace the solve at the full chunk batch (batch-major layout),
    # not per padded row
    monkeypatch.setenv("FLINK_MS_ALS_SOLVER", "pallas")
    fused_cp = A.als_fit(u, i, r, cfg, mesh, init=(uf0, itf0))
    monkeypatch.delenv("FLINK_MS_ALS_SOLVER")
    np.testing.assert_allclose(
        fused_cp.user_factors, plain.user_factors, rtol=1e-4, atol=1e-6
    )
    # fused composes with the bf16 exchange dtype: same answer as the
    # UNFUSED bf16 run (bf16 vs f32 convergence itself is pinned in
    # test_bf16_exchange_converges_close_to_f32)
    monkeypatch.delenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES")
    cfg_bf = A.ALSConfig(num_factors=k, iterations=3, lambda_=0.1,
                         exchange_dtype="bfloat16")
    fused_bf = A.als_fit(u, i, r, cfg_bf, mesh, init=(uf0, itf0))
    monkeypatch.delenv("FLINK_MS_ALS_FUSED")
    plain_bf = A.als_fit(u, i, r, cfg_bf, mesh, init=(uf0, itf0))
    np.testing.assert_allclose(
        fused_bf.user_factors, plain_bf.user_factors, rtol=1e-4, atol=1e-6
    )


def test_fused_solve_matches_unfused_implicit(rng, monkeypatch):
    """Fused mode in implicit/HKV mode: the psum'd Gramian is added per
    chunk instead of to the materialized tensor — same factors."""
    u, i, r = _synthetic(rng, n_users=40, n_items=30, k_true=3)
    r = np.abs(r)  # implicit confidence weights are nonnegative counts
    k = 4
    uf0 = rng.normal(size=(40, k)).astype(np.float32)
    itf0 = rng.normal(size=(30, k)).astype(np.float32)
    cfg = A.ALSConfig(num_factors=k, iterations=2, lambda_=0.1,
                      implicit=True, alpha=10.0)
    mesh = make_mesh(4)
    plain = A.als_fit(u, i, r, cfg, mesh, init=(uf0, itf0))
    monkeypatch.setenv("FLINK_MS_ALS_FUSED", "1")
    fused = A.als_fit(u, i, r, cfg, mesh, init=(uf0, itf0))
    # fp32 tolerance: the fused implicit path accumulates the psum'd
    # Gramian in a different association order, and the exact rounding
    # depends on which compiled variants already sit in the jit cache —
    # at 1e-4/1e-6 this comparison is order-of-tests sensitive (a few
    # elements land near 5e-6 abs / 3e-4 rel in a full-module run)
    np.testing.assert_allclose(
        fused.user_factors, plain.user_factors, rtol=1e-3, atol=1e-5
    )
    np.testing.assert_allclose(
        fused.item_factors, plain.item_factors, rtol=1e-3, atol=1e-5
    )


def test_skewed_degrees_match_numpy(rng):
    """Power-law degree distribution (one super-popular item, many
    degree-1 users — the ML-20M shape) must bucket correctly: one
    iteration still matches the per-row normal-equation spec."""
    n_users, n_items, k, lam = 60, 10, 3, 0.2
    # item 0 is in every user's list; other items are rare; several users
    # rate exactly one item (narrowest bucket, heavy pad)
    u_list, i_list = [], []
    for uu in range(n_users):
        u_list.append(uu)
        i_list.append(0)
        if uu % 3 == 0:  # two-thirds of users are degree-1
            for extra in range(1 + uu % 7):
                u_list.append(uu)
                i_list.append(1 + (uu + extra) % (n_items - 1))
    u = np.array(u_list)
    i = np.array(i_list)
    r = rng.uniform(1, 5, len(u))
    uf0 = rng.normal(size=(n_users, k)).astype(np.float32)
    itf0 = rng.normal(size=(n_items, k)).astype(np.float32)
    for blocks in (1, 4):
        cfg = A.ALSConfig(num_factors=k, iterations=1, lambda_=lam,
                          weighted_reg=True)
        model = A.als_fit(u, i, r, cfg, make_mesh(blocks), init=(uf0, itf0))
        uf_expect = _numpy_user_halfsweep(u, i, r, itf0, k, lam, True)
        np.testing.assert_allclose(
            model.user_factors, uf_expect, rtol=2e-3, atol=2e-4
        )
        itf_expect = _numpy_user_halfsweep(i, u, r, uf_expect, k, lam, True)
        np.testing.assert_allclose(
            model.item_factors, itf_expect, rtol=2e-3, atol=2e-4
        )


def test_blocks_exceed_devices_runs_and_converges(rng):
    """--blocks > devices (legal in the reference: more blocks than slots,
    ALSImpl.scala:39-41): for ALS the solve is row-exact, so the logical
    block count is a parallelism hint only — mesh_for_blocks spans all
    devices and training must run and converge."""
    from flink_ms_tpu.parallel.mesh import mesh_for_blocks

    u, i, r = _synthetic(rng, n_users=50, n_items=37)
    mesh16 = mesh_for_blocks(16)  # 16 logical blocks on the 8-device mesh
    assert mesh16.devices.size == 8
    cfg = A.ALSConfig(num_factors=5, iterations=6, lambda_=1e-3,
                      weighted_reg=False)
    model = A.als_fit(u, i, r, cfg, mesh16)
    assert A.rmse(model, u, i, r) < 0.05


def test_recovers_low_rank_matrix(rng):
    u, i, r = _synthetic(rng, n_users=60, n_items=45, k_true=3, frac=0.5)
    cfg = A.ALSConfig(num_factors=6, iterations=12, lambda_=1e-3, weighted_reg=False)
    model = A.als_fit(u, i, r, cfg, make_mesh(8))
    assert A.rmse(model, u, i, r) < 0.05


def test_ids_are_preserved_not_dense(rng):
    # raw ids with gaps and large values must round-trip
    u = np.array([5, 1000000, 5, 7])
    i = np.array([3, 3, 900, 900])
    r = np.array([1.0, 2.0, 3.0, 4.0])
    model = A.als_fit(u, i, r, A.ALSConfig(num_factors=2, iterations=2), make_mesh(2))
    assert list(model.user_ids) == [5, 7, 1000000]
    assert list(model.item_ids) == [3, 900]
    assert model.user_factors.shape == (3, 2)


def test_predict_unknown_ids_zero(rng):
    u, i, r = _synthetic(rng, n_users=10, n_items=8)
    model = A.als_fit(u, i, r, A.ALSConfig(num_factors=3, iterations=2), make_mesh(1))
    p = A.predict(model, np.array([0, 9999]), np.array([0, 0]))
    assert p[1] == 0.0
    assert p[0] != 0.0


def test_implicit_mode_ranks_observed_higher(rng):
    # implicit: observed (u,i) pairs should score above unobserved on average
    n_users, n_items = 30, 20
    u, i, _ = _synthetic(rng, n_users=n_users, n_items=n_items, frac=0.3)
    r = np.ones_like(u, dtype=np.float64)  # binary implicit feedback
    cfg = A.ALSConfig(
        num_factors=8, iterations=8, lambda_=0.1, implicit=True, alpha=40.0
    )
    model = A.als_fit(u, i, r, cfg, make_mesh(4))
    obs = set(zip(u.tolist(), i.tolist()))
    all_u, all_i = np.meshgrid(model.user_ids, model.item_ids, indexing="ij")
    scores = A.predict(model, all_u.ravel(), all_i.ravel())
    is_obs = np.array([(a, b) in obs for a, b in zip(all_u.ravel(), all_i.ravel())])
    assert scores[is_obs].mean() > scores[~is_obs].mean() + 0.2


def test_more_iterations_do_not_diverge(rng):
    u, i, r = _synthetic(rng, n_users=40, n_items=30, noise=0.1)
    cfg3 = A.ALSConfig(num_factors=4, iterations=3, lambda_=0.05)
    cfg10 = A.ALSConfig(num_factors=4, iterations=10, lambda_=0.05)
    mesh = make_mesh(2)
    uf0 = np.random.default_rng(1).normal(size=(40, 4)).astype(np.float32)
    itf0 = np.random.default_rng(2).normal(size=(30, 4)).astype(np.float32)
    r3 = A.rmse(A.als_fit(u, i, r, cfg3, mesh, init=(uf0, itf0)), u, i, r)
    r10 = A.rmse(A.als_fit(u, i, r, cfg10, mesh, init=(uf0, itf0)), u, i, r)
    assert r10 <= r3 + 1e-3


def test_multiblock_equals_singleblock_implicit(rng):
    # regression: pad factor rows must not pollute the psum'd Gramian
    u, i, _ = _synthetic(rng, n_users=21, n_items=11, frac=0.4)
    r = np.ones_like(u, dtype=np.float64)
    k = 4
    uf0 = rng.normal(size=(len(set(u.tolist())), k)).astype(np.float32)
    itf0 = rng.normal(size=(len(set(i.tolist())), k)).astype(np.float32)
    cfg = A.ALSConfig(num_factors=k, iterations=2, lambda_=0.1, implicit=True)
    m1 = A.als_fit(u, i, r, cfg, make_mesh(1), init=(uf0, itf0))
    m8 = A.als_fit(u, i, r, cfg, make_mesh(8), init=(uf0, itf0))
    np.testing.assert_allclose(m1.user_factors, m8.user_factors, rtol=5e-2, atol=5e-3)


def test_default_init_pad_rows_zeroed(rng):
    # implicit mode, default init, tiny item count on a wide mesh: result
    # must match a run whose pad rows are explicitly zero
    u, i, _ = _synthetic(rng, n_users=9, n_items=11, frac=0.6)
    r = np.ones_like(u, dtype=np.float64)
    cfg = A.ALSConfig(num_factors=3, iterations=1, lambda_=0.1, implicit=True)
    mesh = make_mesh(4)
    m_default = A.als_fit(u, i, r, cfg, mesh)
    # reconstruct the same init matrices (first n rows of the padded init)
    import jax
    import jax.numpy as jnp

    p = A.prepare_blocked(u, i, r, 4)
    key_u, key_i = jax.random.split(jax.random.PRNGKey(cfg.seed))
    uf0 = np.asarray(A.init_factors(p.users_per_block * 4, 3, key_u, jnp.float32))
    itf0 = np.asarray(A.init_factors(p.items_per_block * 4, 3, key_i, jnp.float32))
    m_pinned = A.als_fit(
        u, i, r, cfg, mesh, init=(uf0[: p.n_users], itf0[: p.n_items])
    )
    np.testing.assert_allclose(
        m_default.user_factors, m_pinned.user_factors, rtol=1e-4, atol=1e-5
    )


def test_staged_fit_matches_fused(rng, tmp_path):
    """--temporaryPath semantics: per-iteration staging produces the same
    factors as the fused loop, and snapshots land at every boundary."""
    u, i, r = _synthetic(rng)
    mesh = make_mesh(2)
    cfg = A.ALSConfig(num_factors=4, iterations=3, lambda_=0.1)
    k = cfg.num_factors
    init = (
        rng.normal(size=(int(u.max()) + 1, k)).astype(np.float32),
        rng.normal(size=(int(i.max()) + 1, k)).astype(np.float32),
    )
    fused = A.als_fit(u, i, r, cfg, mesh, init=init)
    staged_dir = str(tmp_path / "stage")
    staged = A.als_fit(u, i, r, cfg, mesh, init=init,
                       temporary_path=staged_dir)
    np.testing.assert_allclose(
        staged.user_factors, fused.user_factors, rtol=2e-4, atol=2e-5
    )
    import os

    # superseded snapshots are pruned; the newest two remain
    snaps = sorted(n for n in os.listdir(staged_dir) if n.endswith(".npz"))
    assert snaps == ["iter_00002.npz", "iter_00003.npz"]


def test_staged_prunes_orphan_tmp_and_times_steps(rng, tmp_path):
    """A mid-write kill leaves iter_*.npz.tmp orphans; the next staged run
    must clean them up.  A passed StepTimer records one entry per staged
    iteration."""
    import os

    from flink_ms_tpu.utils.profiling import StepTimer

    u, i, r = _synthetic(rng)
    mesh = make_mesh(1)
    staged_dir = tmp_path / "stage"
    staged_dir.mkdir()
    (staged_dir / "iter_00009.npz.tmp").write_bytes(b"partial")
    cfg = A.ALSConfig(num_factors=3, iterations=2, lambda_=0.1)
    timer = StepTimer("als-iteration")
    A.als_fit(u, i, r, cfg, mesh, temporary_path=str(staged_dir),
              step_timer=timer)
    names = os.listdir(staged_dir)
    assert not any(n.endswith(".tmp") for n in names)
    assert len(timer.durations_s) == 2


def test_staged_rerun_with_fewer_iterations_not_overtrained(rng, tmp_path):
    """Re-running with a smaller --iterations must not return the later
    (over-trained) snapshot from a previous longer run."""
    u, i, r = _synthetic(rng)
    mesh = make_mesh(1)
    k = 3
    init = (
        rng.normal(size=(int(u.max()) + 1, k)).astype(np.float32),
        rng.normal(size=(int(i.max()) + 1, k)).astype(np.float32),
    )
    staged_dir = str(tmp_path / "stage")
    cfg5 = A.ALSConfig(num_factors=k, iterations=5, lambda_=0.1)
    cfg2 = A.ALSConfig(num_factors=k, iterations=2, lambda_=0.1)
    A.als_fit(u, i, r, cfg5, mesh, init=init, temporary_path=staged_dir)
    short = A.als_fit(u, i, r, cfg2, mesh, init=init,
                      temporary_path=staged_dir)
    plain2 = A.als_fit(u, i, r, cfg2, mesh, init=init)
    np.testing.assert_allclose(
        short.user_factors, plain2.user_factors, rtol=2e-4, atol=2e-5
    )


def test_staged_fit_resumes_from_snapshot(rng, tmp_path):
    """Killing training mid-run and re-running picks up from the latest
    snapshot instead of starting over."""
    u, i, r = _synthetic(rng)
    mesh = make_mesh(2)
    k = 4
    init = (
        rng.normal(size=(int(u.max()) + 1, k)).astype(np.float32),
        rng.normal(size=(int(i.max()) + 1, k)).astype(np.float32),
    )
    staged_dir = str(tmp_path / "stage")
    cfg2 = A.ALSConfig(num_factors=k, iterations=2, lambda_=0.1)
    cfg5 = A.ALSConfig(num_factors=k, iterations=5, lambda_=0.1)
    # run 2 of 5 iterations, "crash", then run the full 5: identical problem
    # and config identity except iterations, so the resume must kick in
    A.als_fit(u, i, r, cfg2, mesh, init=init, temporary_path=staged_dir)
    resumed = A.als_fit(u, i, r, cfg5, mesh, init=init,
                        temporary_path=staged_dir)
    full = A.als_fit(u, i, r, cfg5, mesh, init=init)
    np.testing.assert_allclose(
        resumed.user_factors, full.user_factors, rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize("pairs, blocks, parent_digest", [
    ("repeated", 1, "145d4516bb755f3a9f7edccb37ee0abe5f28a618"),
    ("repeated", 4, "09c7057c1104001430d7714831c359b6bcd2cd46"),
    ("rated once", 1, "f63bcb9931c03881173ffae648c58d781894c16f"),
    ("rated once", 4, "3b90ac684f9f1c3adc2df953d8c89b1eff8b7663"),
])
@pytest.mark.parametrize("strip", [None, 8])
def test_staging_identity_is_the_one_from_before_the_strip(monkeypatch, strip,
                                                           pairs, blocks,
                                                           parent_digest):
    """A snapshot written before PR 34 (one dummy slot a block) must keep
    resuming: the run's identity hashes the layout with the strip folded
    away, so it reads what commit 0f8ff06 computed for the same ratings
    (the digests are that commit's, all four: ``git archive 0f8ff06``,
    this draw, ``_staging_meta(...)["data"]``), whatever the strip's size.

    THE MARKED CASE, ``repeated``: 390 of the draw's 1,809 ratings repeat a
    (user, item) pair.  Until PR 52 two such ratings lay in whichever order
    an unstable ``argsort`` left them (a matter of the host's numpy and its
    vector units, not of the ratings), and the identity hashed that order.
    They now lie in the input's order and are hashed in ascending order, so
    the identity is the ratings' alone; that commit's digest for them cannot
    be had again, and a snapshot of such ratings from before PR 52 trains
    anew, once (``als_train``'s notes on ``--temporaryPath`` say so).  The
    digests such ratings have from PR 52 on are pinned here instead."""
    if strip is not None:
        monkeypatch.setattr(A, "_PAD_STRIP", strip)
    rng = np.random.default_rng(34)
    u = np.repeat(np.arange(90), rng.integers(1, 40, 90))
    i = rng.integers(0, 50, len(u))
    if pairs == "rated once":
        _, first = np.unique(u * 50 + i, return_index=True)
        first.sort()
        u, i = u[first], i[first]
    r = rng.uniform(1, 5, len(u))
    cfg = A.ALSConfig(num_factors=4, iterations=2, lambda_=0.1)

    def identity(order):
        p = A.prepare_blocked(u[order], i[order], r[order], blocks)
        return A._staging_meta(p, cfg, None, "cpu")["data"]

    digest = identity(np.arange(len(u)))
    if pairs == "rated once":
        assert digest == parent_digest
    else:
        assert digest != parent_digest
        assert digest == {1: "f4883bf99d75f4125d2a60cdefa040cb17caa650",
                          4: "72ef51b6df2e53421ac71748351e8a7b77c9e52f"}[blocks]
    # the ratings', not their order's: the same log shuffled resumes too
    assert identity(rng.permutation(len(u))) == digest


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_ties_ascending_sorts_runs_of_one_slot_and_nothing_else(dtype):
    ix = np.array([[[3, 5, 5, 5, 9, 9, 200, 7],
                    [7, 7, 8, 8, 8, 200, 201, 202]],
                   [[1, 2, 3, 4, 5, 6, 200, 201],
                    [4, 4, 4, 4, 4, 4, 4, 4]]], np.int32)
    v = np.array([[[9, 3, 1, 2, 5, 4, 0, 9],
                   [2, 1, 6, 5, 4, 0, 0, 0]],
                  [[8, 7, 6, 5, 4, 3, 0, 0],
                   [8, 7, 6, 5, 4, 3, 2, 1]]], dtype)
    kept = v.copy()
    got = A._ties_ascending(ix, v)
    assert got.dtype == v.dtype and np.array_equal(v, kept)
    assert np.array_equal(got, np.array(
        [[[9, 1, 2, 3, 4, 5, 0, 9], [1, 2, 4, 5, 6, 0, 0, 0]],
         [[8, 7, 6, 5, 4, 3, 0, 0], [1, 2, 3, 4, 5, 6, 7, 8]]], dtype))
    # (a run ends with its list: the first ends on slot 7 and the next
    # begins with two of it); nothing tied, nothing copied
    assert A._ties_ascending(ix[1, :1], v[1, :1]).base is v


def test_staged_mismatched_snapshot_ignored(rng, tmp_path):
    """A snapshot from a different config (lambda changed) must not resume."""
    u, i, r = _synthetic(rng)
    mesh = make_mesh(1)
    staged_dir = str(tmp_path / "stage")
    cfg_a = A.ALSConfig(num_factors=3, iterations=1, lambda_=0.5)
    cfg_b = A.ALSConfig(num_factors=3, iterations=1, lambda_=0.01)
    A.als_fit(u, i, r, cfg_a, mesh, temporary_path=staged_dir)
    fresh = A.als_fit(u, i, r, cfg_b, mesh, temporary_path=staged_dir)
    plain = A.als_fit(u, i, r, cfg_b, mesh)
    np.testing.assert_allclose(
        fresh.user_factors, plain.user_factors, rtol=2e-4, atol=2e-5
    )

def test_bucket_ladder_bounds_padding(rng, monkeypatch):
    """The geometric width ladder bounds per-list padding by ~ratio: every
    entity lands in the smallest rung >= its degree, and rungs are 8-round
    so the worst-case pad is ratio * degree + 8."""
    import os
    u = np.repeat(np.arange(200), rng.integers(1, 300, 200))
    i = rng.integers(0, 50, len(u))
    r = rng.uniform(1, 5, len(u)).astype(np.float64)
    for ratio in ("1.5", "2.0"):
        monkeypatch.setenv("FLINK_MS_ALS_BUCKET_RATIO", ratio)
        p = A.prepare_blocked(u, i, r, 2)
        deg = np.bincount(u, minlength=200)
        widths = np.asarray(p.u.widths)
        for uu in range(200):
            slot = p.u.perm[uu]
            # find the bucket whose slot range holds this entity
            block = slot // p.u.per_block
            local = slot - block * p.u.per_block
            offsets = np.concatenate([[0], np.cumsum(p.u.rows)])
            j = int(np.searchsorted(offsets, local, side="right") - 1)
            w = widths[j]
            assert w >= deg[uu]
            assert w <= float(ratio) * max(deg[uu], 8) + 8, (w, deg[uu])

def test_implicit_halfsweep_matches_numpy_hkv(rng):
    """One implicit iteration vs the dense Hu-Koren-Volinsky spec:
    x_u = (YtY + sum a*r*y y^T + lam I)^-1 sum (1+a*r) y, YtY over the
    WHOLE catalog."""
    u, i, r = _synthetic(rng, n_users=14, n_items=10)
    r = np.abs(r) + 0.5  # implicit confidences must be positive
    k, lam, alpha = 4, 0.3, 3.0
    uf0 = rng.normal(size=(14, k)).astype(np.float32)
    itf0 = rng.normal(size=(10, k)).astype(np.float32)
    cfg = A.ALSConfig(num_factors=k, iterations=1, lambda_=lam,
                      implicit=True, alpha=alpha)
    model = A.als_fit(u, i, r, cfg, make_mesh(1), init=(uf0, itf0))

    def hkv_halfsweep(row, col, rr, Y, n_rows):
        YtY = Y.T @ Y
        out = np.zeros((n_rows, k))
        for e in range(n_rows):
            sel = row == e
            Ys = Y[col[sel]]
            cw = alpha * rr[sel]
            Amat = YtY + (Ys * cw[:, None]).T @ Ys + lam * np.eye(k)
            b = ((1.0 + alpha * rr[sel])[:, None] * Ys).sum(axis=0)
            out[e] = np.linalg.solve(Amat, b)
        return out

    uf_expect = hkv_halfsweep(u, i, r, itf0.astype(np.float64), 14)
    np.testing.assert_allclose(model.user_factors, uf_expect,
                               rtol=2e-3, atol=2e-4)
    itf_expect = hkv_halfsweep(i, u, r, uf_expect, 10)
    np.testing.assert_allclose(model.item_factors, itf_expect,
                               rtol=2e-3, atol=2e-4)

def test_bench_default_config_matches_f64_reference_rmse(rng):
    """pinning test: the ALS config the benchmark times (all
    shipped solver/precision/exchange defaults) must reach the same train
    RMSE as an exact float64 normal-equation solve at equal iterations from
    the same init — the 'identical RMSE' half of the north star."""
    u, i, r = _synthetic(rng, n_users=50, n_items=40, k_true=4, noise=0.1)
    k, lam, iters = 6, 0.1, 4
    n_u, n_i = int(u.max()) + 1, int(i.max()) + 1
    # init is passed in dense-id order; with this seed every id occurs
    assert len(np.unique(u)) == n_u and len(np.unique(i)) == n_i
    rng2 = np.random.default_rng(3)
    u0 = 0.1 * rng2.standard_normal((n_u, k))
    i0 = 0.1 * rng2.standard_normal((n_i, k))

    uf, itf = u0.copy(), i0.copy()
    for _ in range(iters):
        uf = _numpy_user_halfsweep(u, i, r, itf, k, lam, True)
        itf = _numpy_user_halfsweep(i, u, r, uf, k, lam, True)
    pred = np.sum(uf[u] * itf[i], axis=1)
    rmse_ref = float(np.sqrt(np.mean((r - pred) ** 2)))

    mesh = make_mesh()
    cfg = A.ALSConfig(num_factors=k, iterations=iters, lambda_=lam, seed=42)
    model = A.als_fit(u, i, r, cfg, mesh, init=(u0, i0))
    rmse_bench = A.rmse(model, u, i, r)
    assert abs(rmse_bench - rmse_ref) / rmse_ref < 5e-3, (
        rmse_bench, rmse_ref)


def test_bf16_exchange_converges_close_to_f32(rng):
    """exchange_dtype=bfloat16 (half the all_gather + gather bytes) must
    train to nearly the same factors as full-precision exchange."""
    u, i, r = _synthetic(rng, n_users=40, n_items=30)
    k = 5
    uf0 = rng.normal(size=(40, k)).astype(np.float32)
    itf0 = rng.normal(size=(30, k)).astype(np.float32)
    full = A.als_fit(u, i, r, A.ALSConfig(num_factors=k, iterations=3,
                                          lambda_=0.1),
                     make_mesh(2), init=(uf0, itf0))
    bf16 = A.als_fit(u, i, r, A.ALSConfig(num_factors=k, iterations=3,
                                          lambda_=0.1,
                                          exchange_dtype="bfloat16"),
                     make_mesh(2), init=(uf0, itf0))
    # bf16 has ~3 decimal digits: same solution to ~1e-2 relative
    np.testing.assert_allclose(bf16.user_factors, full.user_factors,
                               rtol=5e-2, atol=5e-3)
    r_full = A.rmse(full, u, i, r)
    r_bf16 = A.rmse(bf16, u, i, r)
    assert abs(r_full - r_bf16) < 0.05


# ---------------------------------------------------------------------------
# warm start (round 13 — the autopilot's retrain path)
# ---------------------------------------------------------------------------

def test_warm_start_zero_iteration_parity(rng):
    """A zero-iteration warm-started fit returns the init verbatim — the
    override feeds the SAME init path the seed draw does, no extra
    transform between the caller's factors and the sweep."""
    u, i, r = _synthetic(rng, n_users=12, n_items=9)
    k = 4
    uf0 = rng.normal(size=(12, k)).astype(np.float32)
    itf0 = rng.normal(size=(9, k)).astype(np.float32)
    model = A.als_fit(
        u, i, r, A.ALSConfig(num_factors=k, iterations=0, lambda_=0.1),
        make_mesh(1), init_user_factors=uf0, init_item_factors=itf0)
    np.testing.assert_allclose(model.user_factors, uf0, rtol=1e-6)
    np.testing.assert_allclose(model.item_factors, itf0, rtol=1e-6)


def test_warm_start_kwargs_validation(rng):
    u, i, r = _synthetic(rng, n_users=12, n_items=9)
    k = 3
    uf0 = rng.normal(size=(12, k)).astype(np.float32)
    itf0 = rng.normal(size=(9, k)).astype(np.float32)
    cfg = A.ALSConfig(num_factors=k, iterations=1, lambda_=0.1)
    mesh = make_mesh(1)
    with pytest.raises(ValueError, match="together"):
        A.als_fit(u, i, r, cfg, mesh, init_user_factors=uf0)
    with pytest.raises(ValueError, match="mutually exclusive"):
        A.als_fit(u, i, r, cfg, mesh, init=(uf0, itf0),
                  init_user_factors=uf0, init_item_factors=itf0)
    with pytest.raises(ValueError, match="shapes"):
        A.als_fit(u, i, r, cfg, mesh,
                  init_user_factors=uf0[:5], init_item_factors=itf0)


def test_warm_start_factors_alignment(rng):
    """warm_start_factors carries known ids over verbatim and seeds novel
    ids from the deterministic cold draw."""
    k = 3
    prev_u = {0: np.full(k, 1.0), 2: np.full(k, 2.0)}
    prev_i = {5: np.full(k, 3.0)}
    user_ids = np.asarray([0, 1, 2])
    item_ids = np.asarray([4, 5])
    uf, itf = A.warm_start_factors(user_ids, item_ids, prev_u, prev_i, k,
                                   seed=7)
    np.testing.assert_allclose(uf[0], 1.0)
    np.testing.assert_allclose(uf[2], 2.0)
    np.testing.assert_allclose(itf[1], 3.0)
    # novel rows come from the seed draw, not zeros (a zero row is a
    # stationary point of the opposite half-sweep)
    assert np.abs(uf[1]).max() > 0
    assert np.abs(itf[0]).max() > 0
    # deterministic in (ids, seed)
    uf2, itf2 = A.warm_start_factors(user_ids, item_ids, prev_u, prev_i,
                                     k, seed=7)
    np.testing.assert_array_equal(uf, uf2)
    np.testing.assert_array_equal(itf, itf2)
    # rank-mismatched carryover rows are ignored, not truncated
    uf3, _ = A.warm_start_factors(
        user_ids, item_ids, {0: np.ones(k + 2)}, prev_i, k, seed=7)
    assert np.abs(uf3[0] - 1.0).max() > 0


def test_warm_start_converges_faster_than_cold(rng):
    """Warm-starting from a near-optimum beats the cold seed init at equal
    iteration count on incrementally grown data — the autopilot's whole
    reason to thread serving factors back into the trainer."""
    u, i, r = _synthetic(rng, n_users=40, n_items=30, k_true=3)
    k = 3
    lam = 0.1
    mesh = make_mesh(1)
    # near-optimum on the first 80% of ratings
    n_seed = int(0.8 * len(r))
    opt = A.als_fit(u[:n_seed], i[:n_seed], r[:n_seed],
                    A.ALSConfig(num_factors=k, iterations=12, lambda_=lam),
                    make_mesh(1))
    prev_u = {int(uu): f for uu, f in zip(opt.user_ids, opt.user_factors)}
    prev_i = {int(ii): f for ii, f in zip(opt.item_ids, opt.item_factors)}
    uf0, itf0 = A.warm_start_factors(
        np.unique(u), np.unique(i), prev_u, prev_i, k, seed=42)
    cfg = A.ALSConfig(num_factors=k, iterations=1, lambda_=lam, seed=42)
    warm = A.als_fit(u, i, r, cfg, mesh,
                     init_user_factors=uf0, init_item_factors=itf0)
    cold = A.als_fit(u, i, r, cfg, mesh)
    assert A.rmse(warm, u, i, r) < A.rmse(cold, u, i, r)
