"""Back-to-back CoCoA outer rounds on the program's own compiled round.

Set-up makes the documents from the seed (`synth_cocoa`), lays them out
(`prepare_svm_blocked`), places them and builds the Gram tensor
(`compile_svm_fit`) and runs rounds 0 and 1 through the very call the window
then repeats: `fit(1, *args, start=r)`, one round a call with its absolute
number, which is how `svm_fit` and the CLI run theirs (and equal to one long
fit: `tests/test_svm.py::test_segmented_fit_bit_identical_to_one_shot`).  The
window counts a round when `block_until_ready` returns for it, with one round
always enqueued ahead, and closes on the first completion at or after
`run.seconds`: `train_iter_s` is the window's wall over that count.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmark import reference_cocoa as ref
from benchmark import synth_cocoa


def step_draws(seed, chains, round_no, steps, rows):
    """(chains, steps) slot indices: the configuration's draw sequence,
    `randint(fold_in(fold_in(fold_in(PRNGKey(seed), chain), round), step), 0,
    rows)`, computed with `jax.random` on the host.  The one thing the check
    takes from jax: the sequence belongs to the configuration, not to the
    mathematics, and threefry gives the same integers on every backend."""
    import jax
    import jax.numpy as jnp

    def chain(c):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(jnp.asarray([seed], jnp.uint32)[0]), c), round_no)
        return jax.vmap(lambda h: jax.random.randint(
            jax.random.fold_in(key, h), (), 0, rows))(jnp.arange(steps))

    with jax.default_device(jax.devices("cpu")[0]):
        return np.asarray(jax.jit(jax.vmap(chain))(jnp.arange(chains)))


def slots_of(seed, n, chains, rows):
    """(chains, rows) example ids, -1 in empty slots: slot s of the flattened
    layout holds example `default_rng(seed).permutation(n)[s]`
    (`prepare_svm_blocked`'s documented assignment)."""
    slots = np.full(chains * rows, -1, np.int64)
    slots[:n] = np.random.default_rng(seed).permutation(n)
    return slots.reshape(chains, rows)


def by_example(alpha, slots, n):
    """The program's (chains, rows) duals -> (n,) by example id."""
    flat, where = np.asarray(alpha, np.float64).reshape(-1), slots.reshape(-1)
    out = np.zeros(n)
    out[where[where >= 0]] = flat[:len(where)][where >= 0]
    return out


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
        indptr, indices, values, labels = synth_cocoa.cocoa_problem(cfg, run.seed)
    data = SparseData(labels=labels, indptr=indptr, indices=indices,
                      values=values, n_features=cfg["features"])
    with run.span("cocoa_prepare_s"):
        problem = prepare_svm_blocked(data, cfg["blocks"], seed=run.seed)
    svm = SVMConfig(
        iterations=1, local_iterations=cfg["local_iterations"],
        regularization=cfg["regularization"], stepsize=cfg["stepsize"],
        seed=run.seed, mode=cfg["mode"], inner=cfg["inner"],
        dtype=jnp.dtype(cfg["dtype"]))

    def round_from(state, r):
        """Round `r` from `state` = (w, alpha): enqueued, not awaited."""
        return fit(1, state[0], *args[1:5], state[1], *args[6:], start=r)

    with run.span("cocoa_build_s"):
        fit, args = compile_svm_fit(problem, svm, mesh)
        shape = problem.idx.shape
        del problem  # the host copy of the padded arrays
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
    print("[rounds] n %d min %.5f median %.5f max %.5f; padded %s" % (
        len(walls), min(walls), float(np.median(walls)), max(walls), shape),
        file=sys.stderr, flush=True)

    run.series["iter_s"] = np.asarray(walls)
    run.counts["iterations"] = len(walls)
    run.attempted = len(walls)
    before, last = host(prev), host(state)
    run.failed = 0 if all(np.isfinite(x).all() for x in last) else len(walls)
    del state, prev, args
    check(run, cfg, data, first, opened, before, last, last_round)


def host(state):
    return tuple(np.asarray(x).astype(np.float64) for x in state)


def check(run, cfg, data, first, opened, before, last, last_round):
    """Two rounds of the program against the float64 reference on every
    chain: round 0 from zero (it owes the program nothing), and the
    window's last round from the state fetched before it.  Then what a
    dropped, doubled or unapplied update would break: the primal-dual
    relation and the box at close, the change of w over the window, and the
    objective's fall."""
    lim = cfg["limits"]
    n, lam = cfg["rows"], cfg["regularization"]
    chains, steps = cfg["blocks"], cfg["local_iterations"]
    rows = -(-n // chains)
    csr = (data.indptr, data.indices, data.values)
    slots = slots_of(run.seed, n, chains, rows)
    rule = dict(mode=cfg["mode"], stepsize=cfg["stepsize"])

    w_ref, a_ref = ref.cocoa_round(
        *csr, data.labels, slots, step_draws(run.seed, chains, 0, steps, rows),
        np.zeros(cfg["features"]), np.zeros(n), lam, **rule)
    run.check("cocoa_first_w_rel_err", ref.rel_err(first[0], w_ref),
              lim["cocoa_first_w_rel_err"])
    run.check("cocoa_first_alpha_rel_err",
              ref.rel_err(by_example(first[1], slots, n), a_ref),
              lim["cocoa_first_alpha_rel_err"])
    w_ref, _ = ref.cocoa_round(
        *csr, data.labels, slots,
        step_draws(run.seed, chains, last_round, steps, rows),
        before[0], by_example(before[1], slots, n), lam, **rule)
    run.check("cocoa_last_w_rel_err", ref.rel_err(last[0], w_ref),
              lim["cocoa_last_w_rel_err"])

    alpha = by_example(last[1], slots, n)
    run.check("cocoa_primal_dual_rel_err", ref.rel_err(
        last[0], ref.primal_of(*csr, alpha, lam, cfg["features"])),
        lim["cocoa_primal_dual_rel_err"])
    ya = data.labels * alpha
    run.check("cocoa_box_violation", max((-ya).max(), (ya - 1.0).max()),
              lim["cocoa_box_violation"])
    run.check("cocoa_w_change",
              np.linalg.norm(last[0] - opened[0]) / np.linalg.norm(opened[0]),
              lim["cocoa_w_change_min"], at_least=True)
    fall = (ref.objective(*csr, data.labels, opened[0], lam)
            - ref.objective(*csr, data.labels, last[0], lam))
    run.check("cocoa_objective_drop", fall, lim["cocoa_objective_drop_min"],
              at_least=True)
