"""Back-to-back CoCoA outer rounds over DENSE examples, on the program's own
compiled round.

`cocoa_rounds`' set-up and window with another synth law (`synth_epsilon`:
every row holds every feature) and another reference
(`reference_cocoa_dense`): the examples are handed to the program as the CLI
hands them, CSR triples in a `SparseData` whose rows are all full, and the
program's own rule (`stores_rows_dense`) has to pick the dense layout for
them; the gauge `tpums_svm_dense_entries` at close is how a run shows that it
did.  The window counts a round when `block_until_ready` returns for it, with
one round always enqueued ahead, and closes on the first completion at or
after `run.seconds`: `train_iter_s` is the window's wall over that count.
"""

# first, so that a program without the dense layout stops here, in seconds,
# and never starts the sparse path's re-layout of 800M entries
from flink_ms_tpu.ops.svm import stores_rows_dense

import sys
import time

import numpy as np

from benchmark import reference_cocoa_dense as ref
from benchmark import synth_epsilon
from benchmark.drivers.cocoa_rounds import by_example, host, slots_of, step_draws


def run(run):
    cfg = run.config
    devices = run.acquire()
    run.apply_patches()
    import jax
    import jax.numpy as jnp

    from flink_ms_tpu.core.formats import SparseData
    from flink_ms_tpu.ops.svm import (SVMConfig, compile_svm_fit,
                                      prepare_svm_blocked)
    from flink_ms_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(devices=devices)
    with run.span("cocoa_synth_s"):
        indptr, indices, values, labels = synth_epsilon.epsilon_problem(
            cfg, run.seed)
    if not stores_rows_dense(cfg["rows"], cfg["features"], len(values),
                             values.dtype.itemsize):
        raise ValueError("this configuration's rows are not dense by the "
                         "program's rule: the cell would time another layout")
    data = SparseData(labels=labels, indptr=indptr, indices=indices,
                      values=values, n_features=cfg["features"])
    with run.span("cocoa_prepare_s"):
        problem = prepare_svm_blocked(data, cfg["blocks"], seed=run.seed)
    svm = SVMConfig(
        iterations=1, local_iterations=cfg["local_iterations"],
        regularization=cfg["regularization"], stepsize=cfg["stepsize"],
        seed=run.seed, mode=cfg["mode"], sigma_prime=cfg["sigma_prime"],
        inner=cfg["inner"], dtype=jnp.dtype(cfg["dtype"]))

    def round_from(state, r):
        """Round `r` from `state` = (w, alpha): enqueued, not awaited."""
        return fit(1, state[0], *args[1:5], state[1], *args[6:], start=r)

    with run.span("cocoa_build_s"):
        fit, args = compile_svm_fit(problem, svm, mesh)
        shape = problem.val.shape
        del problem  # the host copy of the dense rows
        state = jax.block_until_ready(round_from((args[0], args[5]), 0))
        first = host(state)
        state = jax.block_until_ready(round_from(state, 1))
    opened = host(state)

    run.start_trace()
    run.begin_window()
    walls = []
    deadline = run.window[0] + run.seconds
    prev = state
    seen = run.window[0]
    nxt = 2  # the number of the next round to enqueue
    pending = round_from(state, nxt)
    while True:
        ahead = round_from(pending, nxt + 1)
        jax.block_until_ready(pending)
        now = time.perf_counter()
        walls.append(now - seen)
        seen = now
        prev, state, pending = state, pending, ahead
        nxt += 1
        if now >= deadline:
            break
    run.end_window()
    jax.block_until_ready(pending)  # the one enqueued past the window
    del pending, ahead
    last_round = nxt - 1  # the round that made `state` from `prev`
    print("[rounds] n %d min %.5f median %.5f max %.5f; dense %s" % (
        len(walls), min(walls), float(np.median(walls)), max(walls), shape),
        file=sys.stderr, flush=True)

    run.series["iter_s"] = np.asarray(walls)
    run.counts["iterations"] = len(walls)
    run.attempted = len(walls)
    before, last = host(prev), host(state)
    run.failed = 0 if all(np.isfinite(x).all() for x in last) else len(walls)
    del state, prev, args
    check(run, cfg, data, first, opened, before, last, last_round)


def check(run, cfg, data, first, opened, before, last, last_round):
    """`cocoa_rounds.check` against the dense reference: round 0 from zero
    and the window's last round from the state fetched before it, on every
    chain; the primal-dual relation (which adding keeps) and the box at
    close; the change of w over the window and the objective's fall; and
    that every cell of X was held dense."""
    lim = cfg["limits"]
    n, d, lam = cfg["rows"], cfg["features"], cfg["regularization"]
    chains, steps = cfg["blocks"], cfg["local_iterations"]
    rows = -(-n // chains)
    X = data.values.reshape(n, d)  # a view: every row is full and in order
    slots = slots_of(run.seed, n, chains, rows)
    rule = dict(mode=cfg["mode"], stepsize=cfg["stepsize"],
                sigma_prime=cfg["sigma_prime"])

    w_ref, a_ref = ref.cocoa_round(
        X, data.labels, slots, step_draws(run.seed, chains, 0, steps, rows),
        np.zeros(d), np.zeros(n), lam, **rule)
    run.check("cocoa_first_w_rel_err", ref.rel_err(first[0], w_ref),
              lim["cocoa_first_w_rel_err"])
    run.check("cocoa_first_alpha_rel_err",
              ref.rel_err(by_example(first[1], slots, n), a_ref),
              lim["cocoa_first_alpha_rel_err"])
    w_ref, _ = ref.cocoa_round(
        X, data.labels, slots,
        step_draws(run.seed, chains, last_round, steps, rows),
        before[0], by_example(before[1], slots, n), lam, **rule)
    run.check("cocoa_last_w_rel_err", ref.rel_err(last[0], w_ref),
              lim["cocoa_last_w_rel_err"])

    alpha = by_example(last[1], slots, n)
    run.check("cocoa_primal_dual_rel_err",
              ref.rel_err(last[0], ref.primal_of(X, alpha, lam)),
              lim["cocoa_primal_dual_rel_err"])
    ya = data.labels * alpha
    run.check("cocoa_box_violation", max((-ya).max(), (ya - 1.0).max()),
              lim["cocoa_box_violation"])
    run.check("cocoa_w_change",
              np.linalg.norm(last[0] - opened[0]) / np.linalg.norm(opened[0]),
              lim["cocoa_w_change_min"], at_least=True)
    fall = (ref.objective(X, data.labels, opened[0], lam)
            - ref.objective(X, data.labels, last[0], lam))
    run.check("cocoa_objective_drop", fall, lim["cocoa_objective_drop_min"],
              at_least=True)
    held = next((g["value"] for g in run.snap_after["gauges"]
                 if g["name"] == "tpums_svm_dense_entries" and not g["labels"]),
                0)
    run.check("tpums_svm_dense_entries", held, chains * rows * d, at_least=True)
