"""The plain reference of a catalog that GROWS while it is read: the writer's
own log replayed in order over a host copy that gains rows, in numpy alone.
Nothing here imports the program; the text of a row is parsed by numpy.

The log is what the writer did, in journal order (one writer): put u wrote
the row its text parses to under the 0-based row number `ids[u]`.  A number
below the base's row count is a row the catalog was loaded with, and the put
replaces it (the last writer wins); any other is a NEW id, readable from the
put that first wrote it and absent before.  `t_start[u]` is the instant
`append` was called and `t_end[u]` the instant it returned (the write is
acknowledged from then on), both on `perf_counter`.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from benchmark import reference


class Log(NamedTuple):
    ids: np.ndarray      # (U,) 0-based row number each put writes
    rows: np.ndarray     # (U, rank) f32, the payload text parsed
    t_start: np.ndarray  # (U,) append called
    t_end: np.ndarray    # (U,) append returned


class Catalog(NamedTuple):
    base: np.ndarray      # (n, rank): the loaded rows after their last writes
    new_ids: np.ndarray   # (M,) the ids the log added, in the order they came
    new_rows: np.ndarray  # (M, rank): each one's last write


def read_log(lines, t_start, t_end):
    """The first `len(t_end)` journal lines `<id>,I,<f1;...;fk>` (ids are
    1-based row numbers) with the writer's stamps -> Log."""
    ids, payloads = [], []
    for line in lines[:len(t_end)]:
        id_, _, payload = line.split(",", 2)
        ids.append(int(id_) - 1)
        payloads.append(payload.rstrip(";"))
    flat = np.array(";".join(payloads).split(";"), dtype=np.float32)
    return Log(np.array(ids, np.int64), flat.reshape(len(ids), -1),
               np.asarray(t_start, np.float64), np.asarray(t_end, np.float64))


def replay(base, log):
    """The catalog after every put of the log, applied one by one in order:
    a put of a loaded row replaces it in a copy of `base` (made at the first
    such put: a log of new ids alone leaves `base` itself, 8 GB at the
    cell's size), a put of any other id adds that id or replaces what an
    earlier put gave it."""
    out, added = base, {}
    for id_, row in zip(log.ids.tolist(), log.rows):
        if id_ < len(base):
            if out is base:
                out = np.array(base, np.float32)
            out[id_] = row
        else:
            added[id_] = row
    rank = base.shape[1]
    return Catalog(out, np.fromiter(added, np.int64, len(added)),
                   np.array(list(added.values()), np.float32).reshape(-1, rank))


def final_topk(base, log, queries, k):
    """Exact top-k over the replayed catalog -> (ids (Q, k + 1), scores
    (Q, k + 1)), as `reference.topk` gives them: blockwise over the loaded
    rows, the added rows scored in float64 beside the shortlist."""
    grown = replay(base, log)
    ids, scores = reference.topk(grown.base, queries, k)
    if not len(grown.new_ids):
        return ids, scores
    q64 = np.asarray(queries, np.float32).astype(np.float64)
    ids = np.concatenate(
        [ids, np.broadcast_to(grown.new_ids, (len(q64), len(grown.new_ids)))],
        axis=1)
    scores = np.concatenate(
        [scores, q64 @ grown.new_rows.astype(np.float64).T], axis=1)
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k + 1]
    return (np.take_along_axis(ids, order, axis=1),
            np.take_along_axis(scores, order, axis=1))


def versions(base, log, id_):
    """Every version row `id_` has had, oldest first: (row, since, until).
    A version may be read from the instant its append was CALLED (the
    consumer can see the bytes before the call returns) until the append of
    the next write of that id has RETURNED (before that, nobody was told
    the row had changed).  A loaded row is readable from -inf; a new id has
    no version before its first put's call; the last write lasts until
    +inf."""
    mine = np.flatnonzero(log.ids == id_)
    rows = [log.rows[u] for u in mine]
    since = [log.t_start[u] for u in mine]
    if id_ < len(base):
        rows, since = [base[id_]] + rows, [-np.inf] + since
    # each version ends when the put after it is acknowledged
    until = [log.t_end[u] for u in mine][len(mine) + 1 - len(rows):] + [np.inf]
    return list(zip(rows, since, until))


def stale_answer(base, log, query, markers, reply, sent, done, within, err):
    """What is wrong with one answer under bounded staleness, or None.

    `reply`: the (row number, score) pairs returned, best first, for `query`
    sent at `sent` and answered at `done`.  Sound means: every returned
    score is `query . v` to `err` for a version `v` of that id that was
    readable at some instant of [sent - within, done] (an id that no put
    had written by `done` has none: it may not be returned at all); and each
    of the query's `markers` (puts of the log written toward this query)
    that was acknowledged more than `within` before `sent` and not written
    over before `done` is among the returned rows, unless its score is
    within `err` of the last returned one or below it (then it need not
    rank).  A marker acknowledged less than `within` before `sent` is too
    fresh to be owed: an answer may lack it and is not stale.  The bound is
    on the wall clock: nothing is taken out of it.
    """
    q = np.asarray(query, np.float64)
    returned = {}
    for id_, score in reply:
        returned[id_] = score
        fits = [abs(float(v.astype(np.float64) @ q) - score)
                for v, since, until in versions(base, log, id_)
                if since <= done and sent - until <= within]
        if not fits or min(fits) > err:
            return f"row {id_}: score {score} is of no version readable then"
    worst = min(score for _, score in reply)
    for u in markers:
        id_ = int(log.ids[u])
        later = np.flatnonzero(log.ids == id_)
        later = later[later > u]
        if sent - log.t_end[u] <= within or (
                len(later) and log.t_start[later[0]] <= done):
            continue  # too fresh to be owed, or written over meanwhile
        score = float(log.rows[u].astype(np.float64) @ q)
        if id_ not in returned and score > worst + err:
            return f"row {id_}: written {sent - log.t_end[u]:.3f} s before, missing"
    return None
