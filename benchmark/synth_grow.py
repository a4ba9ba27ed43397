"""The inserts and the reads of a catalog that grows while it is read, from
`--seed`: YCSB's core workload D ("read latest": Cooper et al., SoCC 2010,
Table 2).

Insert i adds the NEW id `rows + i` (0-based; its journal id is the 1-based
row number, as `synth.catalog`'s ids are): a fresh row of the catalog's own
law, normal(0, 1/rank), pulled toward one pool query, `+ pull * q_j(i)`, j(i)
uniform over the pool.  With `pull` over the top-k threshold of a unit query
the new row enters that query's top-k: it is the query's marker.

A read follows YCSB's "latest" request distribution: it draws a recency rank
by zipfian 0.99 over all records, rank 0 the newest record, and asks for that
record.  Here a read is a TOPKV, so "asks for that record" is the pool query
the record was written toward: for rank r below the number of inserts
scheduled before the read's intended send time, insert `written - 1 - r`'s
`j`; for a rank past them, a loaded row, pool query `r mod pool` (which
loaded row holds the rank is the sibling's seeded permutation and changes
nothing a TOPKV asks).  Both schedules are the seed's and constant-gap, so
the law needs no word between the reading and the writing process.
"""

from __future__ import annotations

import numpy as np


def inserts(traffic, seed, n_rows, vectors, count):
    """-> (ids (count,) 0-based, pool query of each (count,), values
    (count, rank) f32)."""
    rng = np.random.default_rng([seed, 8])
    toward = rng.integers(0, len(vectors), count)
    rank = vectors.shape[1]
    fresh = rng.standard_normal((count, rank), dtype=np.float32)
    fresh *= np.float32(1.0 / np.sqrt(rank))
    values = fresh + np.float32(traffic["insert_pull"]) * vectors[toward]
    return n_rows + np.arange(count), toward, values.astype(np.float32)


def insert_times(traffic, count):
    """Intended instant of each insert, in seconds after the load starts
    (`lead_s` before the window opens): constant gap, `insert_offset_gaps`
    read gaps after the reads' schedule, as the writer keeps it."""
    return (traffic["insert_offset_gaps"] / traffic["rate_per_s"]
            + np.arange(count) / traffic["insert_rate_per_s"])


def latest_slots(traffic, seed, n_rows, toward, n_reads):
    """The pool slot each of the first `n_reads` reads asks, by the latest
    law over `n_rows` loaded records and the inserts in `toward`."""
    records = n_rows + len(toward)
    cdf = np.cumsum(np.arange(1, records + 1, dtype=np.float64)
                    ** -traffic["read_zipf"])
    rng = np.random.default_rng([seed, 9])
    rank = np.minimum(
        np.searchsorted(cdf, rng.random(n_reads) * cdf[-1], side="left"),
        records - 1)
    t_read = np.arange(n_reads) / traffic["rate_per_s"]
    written = np.searchsorted(insert_times(traffic, len(toward)), t_read,
                              side="left")  # inserts scheduled before the read
    newest_first = np.maximum(written - 1 - rank, 0)
    return np.where(rank < written, toward[newest_first],
                    rank % traffic["pool"]).astype(np.int64)
