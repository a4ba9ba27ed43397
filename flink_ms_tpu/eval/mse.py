"""MSE evaluator — counterpart of ``MSE``
(``als-ms/src/main/java/de/tub/it4bi/modelserving/evaluation/MSE.java``).

Evaluates mean squared error of a ratings set against an ALS model.  Two
sources, matching the reference's deployment shape plus an offline mode:

- **live** (reference parity): queries the serving layer one user per group
  and one item per rating (MSE.java:122-159) through the query client —
  flags ``--jobId --jobManagerHost --jobManagerPort --queryTimeout``.
- **offline** (``--model path[,path...]``): reads model row files directly
  and computes predictions as one batched device op.

Skip semantics preserved from the reference: a missing user drops that
user's whole group (MSE.java:137-139 ``break``), a missing item drops just
that rating (:156-158) — minus the reference's NPE ordering bug (SURVEY.md
Appendix C #7).  Input CSV always skips the first line (MSE.java:43
``ignoreFirstLine()``).
"""

from __future__ import annotations

import sys
from typing import Dict, Optional, Tuple

import numpy as np

from ..core import formats as F
from ..core.params import Params, field_delimiter_from


def _load_model_tables(paths: str) -> Dict[str, np.ndarray]:
    """Read ALS rows from comma-separated paths into a {key: factors} map
    keyed like the serving state: ``"<id>-U"`` / ``"<id>-I"``
    (ALSKafkaConsumer.java:75-82)."""
    table: Dict[str, np.ndarray] = {}
    for path in paths.split(","):
        for line in F.iter_lines(path):
            id_, typ, vec = F.parse_als_row(line)
            table[f"{id_}-{typ}"] = vec
    return table


def rolling_holdout_split(
    users,
    items,
    ratings,
    *,
    fraction: float = 0.2,
    seed: int = 0,
    min_train_per_user: int = 1,
) -> Tuple[np.ndarray, np.ndarray]:
    """Seeded, user-stratified held-out split -> (train_idx, holdout_idx).

    The autopilot's evaluation slice: per user with enough ratings,
    ``fraction`` of them (at least one, never more than leaves
    ``min_train_per_user`` behind) move to the held-out side; users with
    too few ratings keep everything in train.  Stratifying per user
    guarantees every held-out user has train-side ratings — without it,
    ``compute_mse``'s reference skip semantics (a missing user drops its
    whole group) would silently evaluate nothing for users the candidate
    model never trained on, and the candidate-vs-incumbent comparison
    would reward models that forget users.

    Deterministic in (inputs, seed): same triples and seed -> identical
    index arrays, so the incumbent and every candidate are scored on the
    byte-identical slice.  Rolling windows pass ``seed=base + version``
    to rotate which ratings are held out as the window grows.

    Returns positional indices into the input arrays (both sorted
    ascending, disjoint, covering every row).
    """
    users = np.asarray(users)
    n = len(users)
    if not 0.0 < fraction < 1.0:
        raise ValueError(f"fraction must be in (0, 1), got {fraction}")
    if len(np.asarray(items)) != n or len(np.asarray(ratings)) != n:
        raise ValueError("users/items/ratings length mismatch")
    rng = np.random.default_rng(seed)
    holdout: list = []
    order = np.argsort(users, kind="stable")
    sorted_users = users[order]
    # group boundaries over the stable sort: per-user index runs, visited
    # in ascending user order so the rng consumption is input-order
    # independent for a fixed triple set
    starts = np.flatnonzero(
        np.r_[True, sorted_users[1:] != sorted_users[:-1]])
    ends = np.r_[starts[1:], n]
    for s, e in zip(starts, ends):
        grp = order[s:e]
        n_grp = len(grp)
        n_hold = min(max(int(round(fraction * n_grp)), 1),
                     n_grp - min_train_per_user)
        if n_hold <= 0:
            continue
        holdout.extend(rng.choice(grp, size=n_hold, replace=False).tolist())
    holdout_idx = np.sort(np.asarray(holdout, dtype=np.int64))
    mask = np.ones(n, dtype=bool)
    mask[holdout_idx] = False
    return np.flatnonzero(mask), holdout_idx


def compute_mse(
    users: np.ndarray,
    items: np.ndarray,
    ratings: np.ndarray,
    lookup,
    lookup_many=None,
) -> Tuple[Optional[float], int, int]:
    """Reference group/skip semantics over an arbitrary key->factors lookup.

    ``lookup_many`` (optional): batched variant taking a list of keys and
    returning payload-or-None per key.  When given, each user group costs
    ONE round trip (user + all its items in a single MGET) vs the
    reference's one-per-group plus one-per-rating (MSE.java:129-158).
    Skip semantics are unchanged: a missing user still drops the whole
    group, a missing item just its rating.

    Returns (mse | None if nothing scored, n_scored, n_skipped).
    """
    sq_sum = 0.0
    n_scored = 0
    n_skipped = 0
    for u in np.unique(users):
        sel = users == u
        group_items = items[sel]
        group_ratings = ratings[sel]
        if lookup_many is not None:
            keys = [f"{u}-U"] + [f"{it}-I" for it in group_items]
            payloads = lookup_many(keys)
            uf = payloads[0]
            item_payloads = payloads[1:]
        else:
            uf = lookup(f"{u}-U")
            item_payloads = None
        if uf is None:
            print(f"No record found for the user ID: {u}-U", file=sys.stderr)
            n_skipped += int(sel.sum())
            continue
        for j, (it, r) in enumerate(zip(group_items, group_ratings)):
            itf = item_payloads[j] if item_payloads is not None else lookup(f"{it}-I")
            if itf is None:
                print(
                    f"No record found for the itemID query: {it}-I", file=sys.stderr
                )
                n_skipped += 1
                continue
            pred = float(np.dot(uf, itf))
            sq_sum += (r - pred) ** 2
            n_scored += 1
    return (sq_sum / n_scored if n_scored else None), n_scored, n_skipped


def _compute_mse_offline_batched(
    users, items, ratings, table: Dict[str, np.ndarray]
) -> Tuple[Optional[float], int, int]:
    """Same semantics as compute_mse, but predictions in one device op."""
    from ..ops.als import ALSModel, predict
    from ..parallel.mesh import acquire_devices

    acquire_devices()

    def numeric_ids(suffix: str):
        out = set()
        for key in table:
            if key.endswith(suffix):
                id_part = key[: -len(suffix)]
                # model dumps legitimately contain the MEAN cold-start row
                # (ALSMeanVector.scala:35); only numeric ids are scoreable
                if id_part.lstrip("-").isdigit():
                    out.add(int(id_part))
        return sorted(out)

    u_ids = numeric_ids("-U")
    i_ids = numeric_ids("-I")
    if not u_ids or not i_ids:
        return None, 0, len(ratings)
    uf = np.stack([table[f"{u}-U"] for u in u_ids])
    itf = np.stack([table[f"{i}-I"] for i in i_ids])
    model = ALSModel(
        user_ids=np.asarray(u_ids),
        item_ids=np.asarray(i_ids),
        user_factors=uf,
        item_factors=itf,
    )
    known_u = np.isin(users, model.user_ids)
    known_i = np.isin(items, model.item_ids)
    ok = known_u & known_i
    preds = predict(model, users[ok], items[ok])
    err = ratings[ok] - preds
    n_scored = int(ok.sum())
    return (
        (float(np.mean(err * err)) if n_scored else None),
        n_scored,
        int((~ok).sum()),
    )


def run(params: Params, lookup=None) -> Optional[float]:
    delim = field_delimiter_from(params, default="tab")
    users, items, ratings = F.read_ratings(
        params.get_required("input"), field_delimiter=delim, ignore_first_line=True
    )

    if params.has("model"):
        table = _load_model_tables(params.get_required("model"))
        mse, n_scored, n_skipped = _compute_mse_offline_batched(
            users, items, ratings, table
        )
    else:
        lookup_many = None
        if lookup is None:
            from ..serve.client import QueryClient

            from ..serve.registry import resolve_endpoint

            mse_host, mse_port = resolve_endpoint(params)
            client = QueryClient(
                host=mse_host,
                port=mse_port,
                timeout_s=params.get_int("queryTimeout", 5),
            )

            def _parse(payload):
                if payload is None:
                    return None
                # serving values are the factor payload "f1;f2;..."
                return np.asarray([float(t) for t in payload.split(";") if t])

            def lookup(key: str):
                return _parse(client.query_state("ALS_MODEL", key))

            if params.get_bool("batchedLookups", True):
                # one MGET round trip per user group (vs one per rating)
                def lookup_many(keys):
                    return [
                        _parse(p)
                        for p in client.query_states("ALS_MODEL", keys)
                    ]

        mse, n_scored, n_skipped = compute_mse(
            users, items, ratings, lookup, lookup_many=lookup_many
        )

    if n_skipped:
        print(f"skipped {n_skipped} ratings with missing keys", file=sys.stderr)
    if mse is None:
        print("No predictions could be made (empty model?)", file=sys.stderr)
        return None
    if params.has("output"):
        F.write_lines(params.get_required("output"), [repr(float(mse))])
    else:
        print("Printing result to stdout. Use --output to specify output path.")
        print(mse)
    return mse


def main(argv=None) -> None:
    run(Params.from_args(sys.argv[1:] if argv is None else argv))


if __name__ == "__main__":
    main()
