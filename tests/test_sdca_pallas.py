"""The SDCA steps of a CoCoA round as a Pallas kernel (ops/sdca_pallas.py).

The kernel runs the update sequence of ``ops/svm.chain_sdca_gram`` with
every access a select against the hoisted draw, so interpreted on the CPU
its Δα is the XLA step's bit for bit, alone and inside whole fits; who takes
it is ``ops/svm.resolve_step``.  The TPU lowering cases live beside the
other kernels' in tests/test_cholesky_pallas.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ms_tpu.core.formats import SparseData
from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.ops import svm
from flink_ms_tpu.ops.sdca_pallas import fits_vmem, sdca_steps_lanes
from flink_ms_tpu.ops.svm import (SVMConfig, chain_sdca_gram,
                                  compile_svm_fit, hoisted_draws,
                                  prepare_svm_blocked, resolve_step, svm_fit)
from flink_ms_tpu.parallel.mesh import make_mesh


def _round_up(x, m):
    return -(-x // m) * m


def _chains(rng, C, h_rows, pad_rows):
    """C chains of h_rows rows, the last ``pad_rows`` of each a pad (label 0,
    squared norm 0, a zero Gram row and column); α inside its box."""
    X = rng.normal(size=(C, h_rows, 24)).astype(np.float32)
    X[:, h_rows - pad_rows:] = 0
    label = np.sign(rng.normal(size=(C, h_rows))).astype(np.float32)
    label[:, h_rows - pad_rows:] = 0
    return dict(
        gram=np.einsum("chd,cgd->chg", X, X).astype(np.float32),
        sqn=np.einsum("chd,chd->ch", X, X).astype(np.float32),
        label=label,
        alpha=(label * rng.uniform(0, 1, (C, h_rows))).astype(np.float32),
        wx0=rng.normal(size=(C, h_rows)).astype(np.float32))


def _kernel_dalpha(c, keys, steps, lam_n, sigma_p):
    """The round's own hand-off: draws hoisted, state chain-minor, Δα back."""
    C, h_rows = c["label"].shape
    hp, cp = _round_up(h_rows, 8), _round_up(C, 128)
    j_all = hoisted_draws(keys, steps, h_rows)

    def lanes(x, rows=hp):
        return jnp.pad(jnp.asarray(x).T,
                       ((0, rows - x.shape[1]), (0, cp - C)))

    gram_t = jnp.pad(jnp.transpose(c["gram"], (1, 2, 0)),
                     ((0, 0), (0, hp - h_rows), (0, cp - C)))
    da = sdca_steps_lanes(
        lanes(j_all, _round_up(steps, 8)), gram_t, lanes(c["wx0"]),
        lanes(c["label"]), lanes(c["sqn"]), lanes(c["alpha"]), steps=steps,
        lam_n=lam_n, sigma_p=sigma_p, interpret=True)
    return np.asarray(da)[:h_rows, :C].T


# (H_rows, steps, chains, σ'): more and fewer steps than rows, never a whole
# number of lane blocks, σ' = 1 (avg) and γK (add)
@pytest.mark.parametrize("h_rows, steps, C, sigma_p", [
    (5, 9, 130, 1.0), (5, 3, 300, 300.0), (49, 52, 140, 140.0),
    (49, 40, 70, 1.0), (83, 85, 100, 1.0), (83, 70, 129, 129.0)])
def test_kernel_is_bit_identical_to_the_xla_step(rng, h_rows, steps, C,
                                                 sigma_p):
    c = _chains(rng, C, h_rows, pad_rows=2)
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(7), i))(
        jnp.arange(C))
    lam_n = 1e-3 * C * h_rows
    want = np.asarray(jax.vmap(functools.partial(
        chain_sdca_gram, steps=steps, lam_n=lam_n, sigma_p=sigma_p))(
        c["wx0"], c["gram"], c["label"], c["sqn"], c["alpha"], keys))
    got = _kernel_dalpha(c, keys, steps, lam_n, sigma_p)
    assert np.count_nonzero(want) > C  # the steps moved something
    assert not want[:, h_rows - 2:].any()  # and never a pad row
    np.testing.assert_array_equal(got, want)


# -- whole fits -------------------------------------------------------------------

def _kernel_on_the_cpu(monkeypatch, on=True):
    """The rule keeps the kernel to a TPU; a test that wants it interpreted
    (or the XLA step back) patches the rule, which the fit cache keys on."""
    monkeypatch.setattr(svm, "resolve_step", lambda platform, inner, *a: (
        "kernel" if on and inner == "gram" else "dynamic"))


def _documents(rng, n, d, nnz_row):
    """Rows of ``nnz_row`` distinct features: sparse below d / 2, dense (by
    the program's own rule) where every feature is named."""
    indices = np.concatenate(
        [np.sort(rng.choice(d, nnz_row, replace=False)) for _ in range(n)])
    return SparseData(
        labels=np.sign(rng.normal(size=n)),
        indptr=np.arange(0, (n + 1) * nnz_row, nnz_row),
        indices=indices.astype(np.int32), values=rng.normal(size=n * nnz_row),
        n_features=d)


@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("mode", ["avg", "add"])
@pytest.mark.parametrize("layout", ["sparse", "dense"])
def test_fit_under_the_kernel_is_the_dynamic_fit(rng, monkeypatch, layout,
                                                 mode, devices):
    data = (_documents(rng, 500, 250, 10) if layout == "sparse"
            else _documents(rng, 300, 12, 12))
    problem = prepare_svm_blocked(data, 20, seed=0)  # 20 chains: 3 a device
    assert problem.dense == (layout == "dense")
    cfg = SVMConfig(iterations=5, regularization=1e-3, mode=mode,
                    local_iterations=problem.rows_per_block + 3, inner="gram")
    mesh = make_mesh(devices)
    weights, in_kernel = {}, {}
    for step in ("dynamic", "kernel"):
        _kernel_on_the_cpu(monkeypatch, on=step == "kernel")
        weights[step] = svm_fit(data, cfg, mesh, problem=problem).weights
        in_kernel[step] = obs_metrics.get_registry().gauge(
            "tpums_svm_step_kernel_chains").value
        assert ("Pallas kernel" in svm.layout_report()) == (step == "kernel")
    assert np.abs(weights["dynamic"]).max() > 0
    np.testing.assert_array_equal(weights["kernel"], weights["dynamic"])
    assert in_kernel == {"dynamic": 0, "kernel": -(-20 // devices)}


def test_kernel_fit_chained_in_two_segments_is_one_long_fit(rng, monkeypatch):
    _kernel_on_the_cpu(monkeypatch)
    data = _documents(rng, 400, 200, 8)
    problem = prepare_svm_blocked(data, 12, seed=0)
    cfg = SVMConfig(local_iterations=problem.rows_per_block, mode="add",
                    regularization=1e-3, inner="gram")
    fit, args = compile_svm_fit(problem, cfg, make_mesh(4))
    w_one, a_one = fit(7, *args)
    w, a = fit(3, *args)
    w, a = fit(4, w, *args[1:5], a, *args[6:], start=3)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w_one))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a_one))


# -- who takes the kernel ------------------------------------------------------------

def test_the_vmem_rule_follows_the_rows_a_chain():
    # both cells' chains; the longest chain a lane block holds; one row more
    assert [fits_vmem(h, h) for h in (5, 49, 83, 113, 114)] == [
        True, True, True, True, False]
    assert not fits_vmem(83, 83 * 100)  # the draws count too


@pytest.mark.parametrize("platform, inner, dtype, h_rows, resolved", [
    ("tpu", "gram", jnp.float32, 49, "kernel"),
    ("tpu", "gram", jnp.float32, 83, "kernel"),
    ("cpu", "gram", jnp.float32, 49, "dynamic"),    # every CPU fit
    ("tpu", "scatter", jnp.float32, 49, "dynamic"),  # no Gram step to run
    ("tpu", "gram", jnp.bfloat16, 49, "dynamic"),   # f32 state only
    ("tpu", "gram", jnp.float32, 114, "dynamic"),   # past the VMEM rule
    ("tpu", "gram", jnp.float32, 113, "kernel"),    # the longest chain held
    ("tpu", "gram", np.float32, 83, "kernel"),      # a numpy dtype's name
    ("tpu", "gram", jnp.float16, 49, "dynamic"),
    ("tpu", "scatter", jnp.bfloat16, 114, "dynamic"),
    ("cpu", "scatter", jnp.float32, 49, "dynamic"),
    ("cpu", "gram", jnp.bfloat16, 114, "dynamic"),
    (None, "gram", jnp.float32, 49, "dynamic"),     # a mesh of no platform
])
def test_the_step_resolves_from_what_the_fit_can_see(platform, inner, dtype,
                                                     h_rows, resolved):
    assert resolve_step(platform, inner, dtype, h_rows, h_rows) == resolved


def test_more_local_steps_than_vmem_holds_keep_the_xla_step():
    # the hoisted draws of 100 local passes do not fit beside the Gram block
    assert resolve_step("tpu", "gram", jnp.float32, 83, 83) == "kernel"
    assert resolve_step("tpu", "gram", jnp.float32, 83, 8300) == "dynamic"


def _primitives(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


@pytest.mark.parametrize("step, traced", [(None, False), ("kernel", True)])
def test_a_cpu_fit_traces_the_kernel_only_when_asked(rng, monkeypatch, step,
                                                     traced):
    if step:
        _kernel_on_the_cpu(monkeypatch)
    data = _documents(rng, 200, 100, 6)
    problem = prepare_svm_blocked(data, 8, seed=0)
    cfg = SVMConfig(local_iterations=problem.rows_per_block, inner="gram")
    fit, args = compile_svm_fit(problem, cfg, make_mesh(1))
    found = _primitives(jax.make_jaxpr(lambda *a: fit(1, *a))(*args).jaxpr,
                        set())
    assert ("pallas_call" in found) == traced
    # the Gram tensor lies as its step reads it, and only so
    gram = args[7]
    rows = problem.rows_per_block
    assert gram.shape == ((rows, _round_up(rows, 8), 128) if traced
                          else (8, rows, rows))
