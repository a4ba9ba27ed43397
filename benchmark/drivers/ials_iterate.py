"""Back-to-back implicit-feedback ALS iterations on the program's own
compiled sweep: `als_iterate`'s window over `ALSConfig(implicit=True)`.

Set-up makes the (user, song, play count) triples from the seed
(`synth_ials`), lays them out (`prepare_blocked`), places them and compiles
(`compile_fit`, which also picks each side's solve route from the sizes and
the device's memory and sets the `tpums_als_*` gauges) and runs two
iterations through the very call the window then repeats, `fit_fn(1, ...)`.
The window counts an iteration when `block_until_ready` returns for it, with
one always enqueued ahead, and closes on the first completion at or after
`run.seconds`: `train_iter_s` is the window's wall over that count.  The
spans (`als_prepare_s`, `als_compile_s`), the series (`iter_s`) and the count
(`iterations`) carry `als_iterate`'s names, so the ALS cell's readers serve
this one too.

The checks compare 256 degree-stratified rows a side, the heaviest
included, of four half-sweeps with `reference_ials.hkv_rows` (float64): the
first iteration from the benchmark's own starting factors (its user half
owes the program nothing) and the window's last iteration from the state
fetched before it.  Each half needs the WHOLE other side for Y^T Y, so a
wrong Gramian, a dropped confidence weight or a play count read as 1 moves
every sampled row.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmark import reference, reference_ials, synth_ials
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
        users, items, plays, init = synth_ials.ials_problem(cfg, run.seed)
    with run.span("als_prepare_s"):
        problem = prepare_blocked(users, items, plays, run.chips)
    als = ALSConfig(
        num_factors=cfg["rank"], iterations=1, lambda_=cfg["lambda"],
        implicit=cfg["implicit"], alpha=cfg["alpha"],
        dtype=jnp.dtype(cfg["dtype"]),
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
    check(run, cfg, users, items, plays, init, first, opened, before, last)


def check(run, cfg, users, items, plays, init, first, opened, before, last):
    """Four half-sweeps against the float64 HKV solve, then the change of
    the item factors over the window: a step that returns its state
    unchanged reads 0 there (and fails the last pair besides, because two
    iterations from a random start are far from a fixed point)."""
    lim = cfg["limits"]
    rng = np.random.default_rng([run.seed, 2])
    n = cfg["check_rows"]
    u_rows = reference.stratified_rows(np.bincount(users, minlength=cfg["n_users"]), n, rng)
    i_rows = reference.stratified_rows(np.bincount(items, minlength=cfg["n_items"]), n, rng)
    pairs = [
        ("ials_first_user_rel_err", first[0], u_rows, users, items, init[1]),
        ("ials_first_item_rel_err", first[1], i_rows, items, users, first[0]),
        ("ials_last_user_rel_err", last[0], u_rows, users, items, before[1]),
        ("ials_last_item_rel_err", last[1], i_rows, items, users, last[0]),
    ]
    for name, got, rows, row_of, col_of, other in pairs:
        want = reference_ials.hkv_rows(rows, row_of, col_of, plays, other,
                                       cfg["lambda"], cfg["alpha"])
        run.check(name, reference.worst_row_error(got[rows], want), lim[name])
    moved = np.linalg.norm(last[1] - opened[1]) / np.linalg.norm(opened[1])
    run.check("ials_item_factor_change", moved, lim["ials_item_factor_change_min"],
              at_least=True)
