"""Back-to-back ALS iterations on the program's own compiled sweep.

Set-up makes the ratings from the seed, lays them out (`prepare_blocked`),
compiles (`compile_fit`) and runs two iterations through the very call the
window then repeats; the window counts an iteration when `block_until_ready`
returns for it, and `train_iter_s` is the window's wall over that count.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmark import reference, synth


def run(run):
    cfg = run.config
    devices = run.acquire()
    run.apply_patches()
    import jax
    import jax.numpy as jnp

    from flink_ms_tpu.ops.als import ALSConfig, compile_fit, prepare_blocked
    from flink_ms_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(devices=devices)
    with run.span("als_synth_s"):
        users, items, ratings, init = synth.als_problem(cfg, run.seed)
    with run.span("als_prepare_s"):
        problem = prepare_blocked(users, items, ratings, run.chips)
    als = ALSConfig(
        num_factors=cfg["rank"], iterations=1, lambda_=cfg["lambda"],
        weighted_reg=True, dtype=jnp.dtype(cfg["dtype"]),
        assembly_precision=cfg["assembly_precision"],
        exchange_dtype=cfg["exchange_dtype"])
    with run.span("als_compile_s"):
        fit_fn, dev_args = compile_fit(problem, als, mesh, init=init)
        static = dev_args[2:]
        one = jnp.asarray(1, jnp.int32)
        state = jax.block_until_ready(fit_fn(one, *dev_args))
        del dev_args
    first = dense(problem, state, cfg["rank"])
    state = jax.block_until_ready(fit_fn(one, *state, *static))
    opened = dense(problem, state, cfg["rank"])

    run.start_trace()
    run.begin_window()
    walls = []
    deadline = run.window[0] + run.seconds
    prev = state
    # One iteration is always enqueued ahead of the one awaited, as the
    # program's own `fit_fn(n, ...)` runs its n iterations in one dispatch:
    # the host's dispatch (1.5 ms, and a per-process 3.5 ms on some runs)
    # stays off the device's critical path.  An iteration's wall is the time
    # between two completions.
    seen = run.window[0]
    pending = fit_fn(one, *state, *static)
    while True:
        ahead = fit_fn(one, *pending, *static)
        jax.block_until_ready(pending)
        now = time.perf_counter()
        walls.append(now - seen)
        seen = now
        prev, state, pending = state, pending, ahead
        if now >= deadline:
            break
    run.end_window()
    jax.block_until_ready(pending)  # the one enqueued past the window
    del pending, ahead
    order = np.argsort(walls)[::-1][:6]
    print("[iters] n %d min %.5f median %.5f max %.5f; longest (index: s) %s" % (
        len(walls), min(walls), float(np.median(walls)), max(walls),
        ", ".join("%d: %.5f" % (i, walls[i]) for i in order)),
        file=sys.stderr, flush=True)

    run.series["iter_s"] = np.asarray(walls)
    run.counts["iterations"] = len(walls)
    run.attempted = len(walls)
    before, last = dense(problem, prev, cfg["rank"]), dense(problem, state, cfg["rank"])
    del state, prev, static
    check(run, cfg, users, items, ratings, init, first, opened, before, last)


def dense(problem, state, k):
    """Factor shards -> dense-id (n, k) host arrays, as `als_fit`'s tail does."""
    uf, itf = (np.asarray(x).reshape(-1, k) for x in state)
    return uf[problem.u.perm], itf[problem.i.perm]


def check(run, cfg, users, items, ratings, init, first, opened, before, last):
    """Sampled rows of four half-sweeps against the float64 ridge solve: the
    first iteration from the benchmark's own starting factors (the user half
    owes nothing to the program), and the window's last iteration from the
    state before it.  A step that returns its state unchanged fails the last
    pair, because one iteration from a random start is far from a fixed
    point; the change of the item factors over the window is printed too."""
    lim = cfg["limits"]
    rng = np.random.default_rng([run.seed, 2])
    n = cfg["check_rows"]
    u_rows = reference.stratified_rows(np.bincount(users, minlength=cfg["n_users"]), n, rng)
    i_rows = reference.stratified_rows(np.bincount(items, minlength=cfg["n_items"]), n, rng)
    lam = cfg["lambda"]
    pairs = [
        ("als_first_user_rel_err", first[0], u_rows, users, items, init[1]),
        ("als_first_item_rel_err", first[1], i_rows, items, users, first[0]),
        ("als_last_user_rel_err", last[0], u_rows, users, items, before[1]),
        ("als_last_item_rel_err", last[1], i_rows, items, users, last[0]),
    ]
    for name, got, rows, row_of, col_of, other in pairs:
        want = reference.ridge_rows(rows, row_of, col_of, ratings, other, lam)
        run.check(name, reference.worst_row_error(got[rows], want), lim[name])
    moved = np.linalg.norm(last[1] - opened[1]) / np.linalg.norm(opened[1])
    run.check("als_item_factor_change", moved, lim["als_item_factor_change_min"],
              at_least=True)
