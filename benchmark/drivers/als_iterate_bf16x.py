"""Back-to-back explicit ALS iterations under a bfloat16 factor exchange on
the program's own compiled sweep: `als_iterate`'s set-up and window over a
configuration that states `exchange_dtype: "bfloat16"`, which is what
`als_train` resolves to on a TPU (`ops/als.resolve_exchange`) and what
`resolve_assembly` sends to the einsum pair.

`als_iterate.check` compares with `reference.ridge_rows`, which solves from
the unrounded factors: a sound run of this path reads 2.1e-3 to 1.4e-2
against it, and a limit loose enough to pass that would pass a bf16 sum too.
So this driver's `check` hands the same four half-sweeps to
`reference_als_bf16.ridge_rows_rounded`, which rounds the opposite side's
factors where the configuration's guarantee says the program does, and the
limits can stay where f32 arithmetic puts them.  The spans (`als_synth_s`,
`als_prepare_s`, `als_compile_s`), the series (`iter_s`), the count
(`iterations`) and the checks carry `als_iterate`'s names, so the ALS
cells' readers serve this one too.  `correct` reads no gauge of the program,
so a program from before `tpums_als_einsum_entries` runs the cell.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmark import reference, reference_als_bf16, synth
from benchmark.drivers.als_iterate import dense


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
    k = cfg["rank"]
    with run.span("als_compile_s"):
        fit_fn, dev_args = compile_fit(problem, als, mesh, init=init)
        static = dev_args[2:]
        one = jnp.asarray(1, jnp.int32)
        state = jax.block_until_ready(fit_fn(one, *dev_args))
        del dev_args
    first = dense(problem, state, k)
    state = jax.block_until_ready(fit_fn(one, *state, *static))
    opened = dense(problem, state, k)

    run.start_trace()
    run.begin_window()
    walls = []
    deadline = run.window[0] + run.seconds
    prev = state
    # as `als_iterate`: one iteration always enqueued ahead of the one
    # awaited, an iteration's wall the time between two completions
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
    before, last = dense(problem, prev, k), dense(problem, state, k)
    run.failed = 0 if all(np.isfinite(x).all() for x in last) else len(walls)
    del state, prev, static
    check(run, cfg, users, items, ratings, init, first, opened, before, last)


def check(run, cfg, users, items, ratings, init, first, opened, before, last):
    """`als_iterate.check`'s four half-sweeps and its change of the item
    factors over the window, under its names, against the float64 ridge
    solve from the ROUNDED opposite factors: the first iteration from the
    benchmark's own starting factors (the user half owes the program
    nothing, not even the rounding), the window's last from the state
    fetched before it."""
    # (the `bf16_state` control's factors arrive as bfloat16 arrays)
    first, opened, before, last = (
        tuple(np.asarray(x, np.float32) for x in pair)
        for pair in (first, opened, before, last))
    lim = cfg["limits"]
    rng = np.random.default_rng([run.seed, 2])
    n = cfg["check_rows"]
    u_rows = reference.stratified_rows(np.bincount(users, minlength=cfg["n_users"]), n, rng)
    i_rows = reference.stratified_rows(np.bincount(items, minlength=cfg["n_items"]), n, rng)
    pairs = [
        ("als_first_user_rel_err", first[0], u_rows, users, items, init[1]),
        ("als_first_item_rel_err", first[1], i_rows, items, users, first[0]),
        ("als_last_user_rel_err", last[0], u_rows, users, items, before[1]),
        ("als_last_item_rel_err", last[1], i_rows, items, users, last[0]),
    ]
    for name, got, rows, row_of, col_of, other in pairs:
        want = reference_als_bf16.ridge_rows_rounded(
            rows, row_of, col_of, ratings, other, cfg["lambda"])
        run.check(name, reference.worst_row_error(got[rows], want), lim[name])
    moved = np.linalg.norm(last[1] - opened[1]) / np.linalg.norm(opened[1])
    run.check("als_item_factor_change", moved, lim["als_item_factor_change_min"],
              at_least=True)
