"""The sharded exact tier as the four-chip benchmark cell drives it
(`bigann-t2i-100m-1of6.topk-paced-4chip`): `bulk_load` + `topk_many` over
FOUR of the host-platform devices the conftest forces, against the
benchmark's own blockwise numpy reference, which knows nothing of shards,
padding or the merge.  One parametrised test: every case builds (or shares)
an index through the normal path and names what it checks."""

from functools import partial

import numpy as np
import pytest

from benchmark import reference
from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.parallel.mesh import (
    BLOCK_AXIS, make_mesh, row_bucket, row_capacity)
from flink_ms_tpu.serve import topk as topk_mod
from flink_ms_tpu.serve.table import ModelTable

SHARDS = 4
RANK = 16
SCOPES = ("topk.shard_score", "topk.shard_select", "topk.merge")


@pytest.fixture
def four(monkeypatch):
    """The index's mesh cut to four devices, as on a 2x2 host; the sharded
    layout forced, since these catalogs are under the production floor."""
    import jax

    monkeypatch.setitem(topk_mod._index_mesh_cache, "",
                        make_mesh(devices=jax.devices()[:SHARDS]))
    monkeypatch.setenv("TPUMS_TOPK_SHARDED", "1")
    monkeypatch.setenv("TPUMS_TOPK_TIER", "exact")


def catalog(n, seed):
    rng = np.random.default_rng([seed, n])
    rows = rng.standard_normal((n, RANK), dtype=np.float32) / np.float32(4.0)
    return [str(i + 1) for i in range(n)], rows


def queries(b, seed):
    q = np.random.default_rng([seed, 1]).standard_normal(
        (b, RANK), dtype=np.float32)
    return q / np.linalg.norm(q, axis=1, keepdims=True)


def build(ids, rows):
    index = topk_mod.DeviceFactorIndex(ModelTable(), "-I")
    index.bulk_load(ids, rows)
    assert index._is_sharded and index._n_pad == row_bucket(len(ids), SHARDS)
    return index


def agrees(index, rows, q, k):
    """The index's answer equals the reference's: the same rows wherever the
    reference's scores are distinct, the same scores, no pad row."""
    got = index.topk_many(q, k)
    k_eff = min(k, len(rows))
    assert [len(g) for g in got] == [k_eff] * len(q)
    got_ids = np.array([[int(item) - 1 for item, _ in g] for g in got])
    got_scores = np.array([[score for _, score in g] for g in got])
    assert got_ids.min() >= 0 and got_ids.max() < len(rows)  # never a pad row
    ref_ids, ref_scores = reference.topk(rows, q, k_eff)
    if ref_ids.shape[1] == k_eff:  # k = every row: no runner-up column
        ref_ids = np.pad(ref_ids, ((0, 0), (0, 1)), constant_values=-1)
        ref_scores = np.pad(ref_scores, ((0, 0), (0, 1)), constant_values=-1e30)
    err, wrong, clear = reference.compare_topk(
        got_ids, got_scores, ref_ids, ref_scores, 1e-6)
    assert err < 1e-5 and wrong == 0 and clear > 0.8 * got_ids.size
    return got_ids


def gauge(name):
    return next(g["value"] for g in obs_metrics.get_registry().snapshot()["gauges"]
                if g["name"] == name and not g["labels"])


def counter(name):
    return obs_metrics.get_registry().counter(name).value


def ragged_rows(n):
    """A row count that is no multiple of the shard count (4,097 leaves the
    third shard one real row and the fourth none): pad rows never surface,
    and the gauge reads the pad."""
    ids, rows = catalog(n, 11)
    index = build(ids, rows)
    agrees(index, rows, queries(5, n), 10)
    assert gauge("tpums_topk_pad_rows") == row_bucket(n, SHARDS) - n > 0
    assert gauge("tpums_topk_shards") == SHARDS
    assert gauge("tpums_topk_shard_rows") == row_bucket(n, SHARDS) // SHARDS


def planted(per_shard):
    """Winners planted shard by shard: `per_shard[s]` rows of shard s get the
    largest products with the query, so the top-k lies where the case says."""
    n = 4059  # 1,024 rows a shard, the last one 987 real and 37 pad
    ids, rows = catalog(n, 13)
    q = queries(1, 5)
    per = row_bucket(n, SHARDS) // SHARDS
    rng = np.random.default_rng(17)
    want = []
    for s, count in enumerate(per_shard):
        real = range(s * per, min((s + 1) * per, n))
        for pos in rng.choice(real, count, replace=False):
            rows[pos] = q[0] * np.float32(3.0 + len(want) * 0.25)
            want.append(int(pos))
    index = build(ids, rows)
    got = agrees(index, rows, q, len(want))
    assert sorted(got[0].tolist()) == sorted(want)
    assert {p // per for p in got[0].tolist()} == {
        s for s, count in enumerate(per_shard) if count}


def deep_k(n, k):
    """k above a shard's real rows (and above the catalog): a shard's local
    top-k then holds pad rows, and the merge must leave them behind."""
    ids, rows = catalog(n, 19)
    index = build(ids, rows)
    assert k > -(-n // SHARDS)
    agrees(index, rows, queries(3, n + k), k)


_shared = {}


def batch_shape(b):
    """Every batch shape the batcher can hand down, on one shared index:
    six compiled programs (1, 2, 4 ... 32) answer all of them."""
    if "index" not in _shared:
        ids, rows = catalog(4059, 23)
        _shared["rows"], _shared["index"] = rows, build(ids, rows)
    before = counter("tpums_topk_sharded_frames_total")
    agrees(_shared["index"], _shared["rows"], queries(b, 100 + b), 10)
    assert counter("tpums_topk_sharded_frames_total") == before + 1


def scopes_in_the_program():
    """The three scopes reach the lowered program; the single-device
    programs keep their own two and gain none."""
    import jax.numpy as jnp

    ids, rows = catalog(1026, 29)
    index = build(ids, rows)
    text = topk_mod._sharded_topk_program(index._mesh).lower(
        index._matrix, index._live, jnp.zeros((8, RANK)), 10
    ).compile().as_text()  # the compiled HLO names each operation's path
    for op in ("topk.shard_score/dot_general", "topk.shard_score/axis_index",
               "topk.shard_score/jit(_where)/select_n",
               "topk.shard_select/top_k",
               "topk.merge/all_gather", "topk.merge/top_k",
               "topk.merge/jit(take_along_axis)/gather"):
        assert f'op_name="jit(sharded_topk)/shard_map/{op}"' in text
    assert "/topk.score/" not in text and "/topk.select/" not in text


def counters_and_gauges(monkeypatch):
    """The gauges follow the installed layout, sharded or not; the frame
    counter counts the shard_map program's runs and nothing else."""
    ids, rows = catalog(1026, 31)
    index = build(ids, rows)
    assert (gauge("tpums_topk_shards"), gauge("tpums_topk_shard_rows"),
            gauge("tpums_topk_pad_rows")) == (4, 512, 2048 - 1026)
    before = counter("tpums_topk_sharded_frames_total")
    index.topk_many(queries(3, 1), 10)
    index.topk(queries(1, 2)[0], 10)  # a lone query rides the frame program
    index.warm_batch_shapes(10, 32)   # six more
    assert counter("tpums_topk_sharded_frames_total") == before + 8
    monkeypatch.setenv("TPUMS_TOPK_SHARDED", "0")
    single = topk_mod.DeviceFactorIndex(ModelTable(), "-I")
    single.bulk_load(ids, rows)
    assert not single._is_sharded
    # one device: the live rows and the spare ones for ids to come
    assert (gauge("tpums_topk_shards"), gauge("tpums_topk_shard_rows"),
            gauge("tpums_topk_pad_rows")) == (
        1, row_capacity(1026), row_capacity(1026) - 1026)
    before = counter("tpums_topk_sharded_frames_total")
    single.topk_many(queries(3, 1), 10)
    assert counter("tpums_topk_sharded_frames_total") == before


def built_from_the_unpadded_rows(n, monkeypatch):
    """`_pack` against the bucket (4 x 1,024): the padded matrix exists on
    the devices only.  Whole shards are put from views of `rows`; only a
    shard that is not all real rows gets a host buffer, and the gauge
    counts those buffers' bytes.  What the devices hold is what the padded
    host copy used to give them, bit for bit."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    ids, rows = catalog(n, 37)
    n_pad = row_bucket(n, SHARDS)
    per = n_pad // SHARDS
    made = []  # elements of every host array numpy is asked to make
    with monkeypatch.context() as patch:
        for name in ("zeros", "empty"):
            def recording(shape, *args, _make=getattr(np, name), **kwargs):
                made.append(int(np.prod(shape)))
                return _make(shape, *args, **kwargs)

            patch.setattr(np, name, recording)
        index = build(ids, rows)
    not_whole = sum(1 for s in range(SHARDS) if (s + 1) * per > n)
    assert not_whole == {4096: 0, 4059: 1, 2049: 2}[n]
    assert gauge("tpums_topk_build_host_copy_bytes") \
        == not_whole * per * RANK * 4
    assert max(made, default=0) < n_pad * RANK  # no (n_pad, k) on the host
    padded = np.zeros((n_pad, RANK), np.float32)
    padded[:n] = rows
    mesh = index._mesh
    assert index._matrix.sharding == NamedSharding(mesh, P(BLOCK_AXIS, None))
    # the pad rows are told from the live ones by the count, on every device
    assert index._live.sharding == NamedSharding(mesh, P())
    assert int(index._live) == n
    assert index._matrix.shape == (n_pad, RANK)
    assert np.array_equal(np.asarray(index._matrix).view(np.uint32),
                          padded.view(np.uint32))
    for shard in index._matrix.addressable_shards:  # each device its own rows
        assert np.array_equal(np.asarray(shard.data), padded[shard.index])
    q = queries(6, n)
    got = index.topk_many(q, 10)
    monkeypatch.setenv("TPUMS_TOPK_SHARDED", "0")
    single = topk_mod.DeviceFactorIndex(ModelTable(), "-I")
    single.bulk_load(ids, rows)
    assert not single._is_sharded
    assert gauge("tpums_topk_build_host_copy_bytes") == 0
    want = single.topk_many(q, 10)
    assert [[i for i, _ in g] for g in got] == [[i for i, _ in w] for w in want]
    np.testing.assert_allclose([[s for _, s in g] for g in got],
                               [[s for _, s in w] for w in want], atol=1e-6)


CASES = [
    *(pytest.param(ragged_rows, (n,), id=f"rows-{n}")
      for n in (1026, 4059, 4097, 5003)),
    *(pytest.param(planted, (tuple(10 if s == t else 0 for t in range(SHARDS)),),
                   id=f"winners-in-shard-{s}") for s in range(SHARDS)),
    pytest.param(planted, ((3, 3, 2, 2),), id="winners-split-3-3-2-2"),
    pytest.param(planted, ((1, 4, 1, 4),), id="winners-split-1-4-1-4"),
    pytest.param(deep_k, (40, 12), id="k-12-over-10-rows-a-shard"),
    pytest.param(deep_k, (40, 40), id="k-every-row"),
    pytest.param(deep_k, (9, 10), id="k-over-the-catalog"),
    *(pytest.param(batch_shape, (b,), id=f"batch-{b}") for b in range(1, 33)),
    pytest.param(scopes_in_the_program, (), id="scopes"),
    pytest.param(counters_and_gauges, None, id="counters-and-gauges"),
    *(pytest.param(partial(built_from_the_unpadded_rows, n), None,
                   id=f"unpadded-rows-{what}")
      for n, what in ((4096, "fill-the-bucket"), (4059, "end-in-the-last-shard"),
                      (2049, "leave-a-shard-all-pad"))),
]


@pytest.mark.parametrize("case, args", CASES)
def test_sharded_exact_tier(case, args, four, monkeypatch):
    case(monkeypatch) if args is None else case(*args)
