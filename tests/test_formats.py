import os

import numpy as np
import pytest

from flink_ms_tpu.core import formats as F


def test_ratings_roundtrip(tmp_path):
    users = np.array([1, 2, 3])
    items = np.array([10, 20, 30])
    ratings = np.array([4.0, 3.5, 1.0])
    p = str(tmp_path / "ratings.csv")
    F.write_ratings(p, users, items, ratings)
    u, i, r = F.read_ratings(p)
    np.testing.assert_array_equal(u, users)
    np.testing.assert_array_equal(i, items)
    np.testing.assert_allclose(r, ratings)


def test_ratings_tab_and_header(tmp_path):
    p = str(tmp_path / "r.tsv")
    with open(p, "w") as f:
        f.write("userId\titemId\trating\n1\t2\t5.0\n7\t8\t2.5\n")
    u, i, r = F.read_ratings(p, field_delimiter="\t", ignore_first_line=True)
    assert list(u) == [1, 7]
    assert list(i) == [2, 8]
    np.testing.assert_allclose(r, [5.0, 2.5])


def test_ratings_directory_of_parts(tmp_path):
    d = tmp_path / "out"
    d.mkdir()
    (d / "1").write_text("1,2,3.0\n")
    (d / "2").write_text("4,5,1.0\n")
    u, i, r = F.read_ratings(str(d))
    assert len(u) == 2


def test_als_row_roundtrip():
    line = F.format_als_row(42, F.USER, [0.5, -1.25, 3.0])
    assert line == "42,U,0.5;-1.25;3.0"
    id_, typ, vec = F.parse_als_row(line)
    assert id_ == "42" and typ == "U"
    np.testing.assert_allclose(vec, [0.5, -1.25, 3.0])


def test_als_model_file_roundtrip(tmp_path):
    p = str(tmp_path / "user_factors")
    mat = np.array([[1.0, 2.0], [3.0, 4.0]])
    F.write_als_model(p, [10, 20], F.USER, mat)
    ids, types, out = F.read_als_model(p)
    assert ids == ["10", "20"]
    assert types == ["U", "U"]
    np.testing.assert_allclose(out, mat)


def test_mean_row():
    assert F.format_mean_row(F.ITEM, [0.5, 0.5]) == "MEAN,I,0.5;0.5"


def test_svm_flat_rows():
    rows = list(F.format_svm_flat_rows(np.array([0.1, -0.2])))
    assert rows == ["1,0.1", "2,-0.2"]
    assert F.parse_svm_flat_row(rows[1]) == (2, -0.2)


def test_svm_range_rows_bucketing():
    # 1-based idx // range: with range=2, idx1=1 -> bucket 0, idx1=2 -> 1,
    # idx1=3 -> 1, idx1=4 -> 2 (matches SVMImpl.scala:42 integer division)
    w = np.array([1.0, 2.0, 3.0, 4.0])
    rows = list(F.format_svm_range_rows(w, 2))
    assert rows == ["0,1:1.0", "1,2:2.0;3:3.0", "2,4:4.0"]
    b, entries = F.parse_svm_range_row(rows[1])
    assert b == 1 and entries == [(2, 2.0), (3, 3.0)]


def test_read_svm_model_flat_and_ranged(tmp_path):
    w = np.array([0.5, 0.0, -1.5])
    flat = str(tmp_path / "flat")
    F.write_lines(flat, F.format_svm_flat_rows(w))
    np.testing.assert_allclose(F.read_svm_model(flat), w)

    ranged = str(tmp_path / "ranged")
    F.write_lines(ranged, F.format_svm_range_rows(w, 1000))
    np.testing.assert_allclose(F.read_svm_model(ranged, partitioned=True), w)


def test_libsvm_parse(tmp_path):
    p = str(tmp_path / "data.libsvm")
    with open(p, "w") as f:
        f.write("+1 1:0.5 3:1.5\n")
        f.write("-1 2:2.0 # a comment\n")
        f.write("\n")
    d = F.read_libsvm(p)
    assert d.n_examples == 2
    assert d.n_features == 3
    np.testing.assert_allclose(d.labels, [1.0, -1.0])
    idx0, val0 = d.row(0)
    assert list(idx0) == [0, 2]  # 1-based on disk -> 0-based
    np.testing.assert_allclose(val0, [0.5, 1.5])
    idx1, val1 = d.row(1)
    assert list(idx1) == [1]


def test_libsvm_rejects_zero_index(tmp_path):
    p = str(tmp_path / "bad.libsvm")
    with open(p, "w") as f:
        f.write("1 0:1.0\n")
    with pytest.raises(ValueError):
        F.read_libsvm(p)


def test_latency_rows():
    assert F.format_als_latency_row(1, 2, 3.5, 12.6) == "1,2,3.5,13"
    assert F.format_svm_latency_row(9, 4, -1.0, 0.4) == "9,4,-1.0,0"


def test_iter_lines_skips_hidden(tmp_path):
    d = tmp_path / "model"
    d.mkdir()
    (d / "part-1").write_text("a\n")
    (d / ".crc").write_text("junk\n")
    (d / "_SUCCESS").write_text("\n")
    assert list(F.iter_lines(str(d))) == ["a"]


def test_ratings_header_skipped_per_file(tmp_path):
    # Flink's CsvInputFormat skips the first line of every file
    d = tmp_path / "parts"
    d.mkdir()
    (d / "1").write_text("u,i,r\n1,2,3.0\n")
    (d / "2").write_text("u,i,r\n4,5,1.0\n")
    u, i, r = F.read_ratings(str(d), ignore_first_line=True)
    assert list(u) == [1, 4]


def test_interior_empty_factor_token_raises():
    with pytest.raises(ValueError):
        F.parse_als_row("7,U,1.0;;2.0")
    # trailing separator still tolerated (Java split semantics)
    _, _, v = F.parse_als_row("7,U,1.0;2.0;")
    assert list(v) == [1.0, 2.0]

def test_range_payload_cache_coherent_and_bounded():
    from flink_ms_tpu.core.formats import RangePayloadCache

    cache = RangePayloadCache(max_entries=2)
    idx, w = cache.lookup("3:0.5;1:0.25;")
    # sorted ascending by index
    assert idx.tolist() == [1, 3] and w.tolist() == [0.25, 0.5]
    # same string -> same (cached) arrays
    idx2, _ = cache.lookup("3:0.5;1:0.25;")
    assert idx2 is idx
    # a republished bucket arrives as a DIFFERENT string: must miss
    idx3, w3 = cache.lookup("3:0.75;1:0.25;")
    assert w3.tolist() == [0.25, 0.75]
    # bounded: inserting past max evicts, no growth
    cache.lookup("7:1.0")
    assert len(cache._cache) <= 2

def test_range_payload_malformed_still_raises():
    """The vectorized fast path must not silently re-pair corrupted rows:
    structure violations raise exactly as the per-token parser did."""
    from flink_ms_tpu.core.formats import parse_svm_range_row

    for bad in ("5,1;2", "5,1:2:3;4", "5,:1;2:3", "5,1:2;3"):
        with pytest.raises(ValueError):
            parse_svm_range_row(bad)


def test_float_formatted_index_rejected_like_exact_path():
    """the fast path must agree with the per-token path on what
    is malformed — a float-shaped index ("3.0:w", "3e0:w") raises, while
    negative/plus-signed integer indices still take the fast path."""
    from flink_ms_tpu.core.formats import parse_svm_range_payload

    for bad in ("3.0:1.5;4:2.0", "3e0:1.5", "4:2.0;0x3:1.0"):
        with pytest.raises(ValueError):
            parse_svm_range_payload(bad)
    # exponent/decimal in the VALUE region stays fast-path legal
    idx, w = parse_svm_range_payload("3:1.5e-2;-4:2.0;+5:.25")
    assert idx.tolist() == [3, -4, 5]
    assert w.tolist() == [0.015, 2.0, 0.25]


def test_range_cache_duplicate_index_last_wins():
    """duplicate feature ids within one payload resolve to the
    LAST occurrence — the dict-parse semantics the range client had before
    the vectorized cache."""
    from flink_ms_tpu.core.formats import RangePayloadCache

    cache = RangePayloadCache()
    w, hit = cache.gather("5:1.0;7:2.0;5:9.0", [5, 7])
    assert w.tolist() == [9.0, 2.0]
    assert hit.all()
