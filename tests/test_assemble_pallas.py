"""Pallas ALS assembly kernel: numerics against a float64 einsum in
interpreter mode, the precision it is asked for, end-to-end ALS parity
with the resolver patched onto the kernel (unfused, fused, chunked), and
the resolver's answers — the bf16-exchange, implicit and CPU paths must
keep the einsum pair.  The TPU cross-lowering cases are beside the
Cholesky kernel's in ``test_cholesky_pallas.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ms_tpu.ops import als as A
from flink_ms_tpu.ops.assemble_pallas import assemble_bucket, tile_sizes
from flink_ms_tpu.parallel.mesh import make_mesh

# rows per width: never a multiple of the kernel's entity tile C, and more
# than one row block where C is small enough to afford it in the interpreter
_ROWS = {8: 13, 24: 13, 64: 13, 144: 53, 328: 29, 1032: 11}


def _bucket(rng, r, w, k):
    y = rng.standard_normal((r, w, k)).astype(np.float32)
    t = rng.uniform(0.5, 5.0, (r, w)).astype(np.float32)
    y[:, w - 3:], t[:, w - 3:] = 0.0, 0.0   # pad entries: zero row, zero rating
    y[1], t[1] = 0.0, 0.0                   # an entity that is all pad
    return y, t


def _einsum64(y, t):
    y, t = y.astype(np.float64), t.astype(np.float64)
    return (np.einsum("rwk,rwl->rkl", y, y), np.einsum("rwk,rw->rk", y, t))


@pytest.mark.parametrize("k", [10, 50, 64])
@pytest.mark.parametrize("w", sorted(_ROWS))
def test_kernel_matches_float64_einsum(rng, w, k):
    """w = 1032 is one full tile of 1024 and a ragged tile of 8: the rows
    the second block reads past the array must not reach A or b."""
    r = _ROWS[w]
    c, wt = tile_sizes(w, k)
    assert r % c and (w <= wt or w % wt)
    y, t = _bucket(rng, r, w, k)
    got_a, got_b = assemble_bucket(
        jnp.asarray(y), jnp.asarray(t), precision="highest", interpret=True)
    want_a, want_b = _einsum64(y, t)
    np.testing.assert_allclose(got_a, want_a, rtol=1e-5,
                               atol=1e-6 * np.abs(want_a).max())
    np.testing.assert_allclose(got_b, want_b, rtol=1e-5,
                               atol=1e-6 * np.abs(want_b).max())
    assert not np.asarray(got_a)[1].any() and not np.asarray(got_b)[1].any()


@pytest.mark.parametrize("w", [24, 1032])
def test_default_precision_is_one_bf16_pass(rng, w):
    """`precision="default"` rounds both operands to bfloat16 and
    accumulates in f32, as the einsum it replaces does on a TPU: the
    benchmark's `bf16_assembly` control must stay wrong."""
    y, t = _bucket(rng, 9, w, 50)
    got_a, got_b = assemble_bucket(
        jnp.asarray(y), jnp.asarray(t), precision="default", interpret=True)

    def rounded(x):
        return np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))

    want_a, want_b = _einsum64(rounded(y), rounded(t))
    np.testing.assert_allclose(got_a, want_a, rtol=1e-5,
                               atol=1e-6 * np.abs(want_a).max())
    np.testing.assert_allclose(got_b, want_b, rtol=1e-5,
                               atol=1e-6 * np.abs(want_b).max())
    full_a, _ = _einsum64(y, t)
    assert np.abs(np.asarray(got_a) - full_a).max() > 1e-4 * np.abs(full_a).max()


def test_unknown_precision_is_refused():
    y = jnp.zeros((8, 8, 4), jnp.float32)
    with pytest.raises(ValueError, match="precision"):
        assemble_bucket(y, y[..., 0], precision="high", interpret=True)


def _kernel_everywhere(platform, y_dtype, dtype, implicit, k,
                       precision="highest"):
    return "einsum" if implicit else "kernel"


@pytest.mark.parametrize("mode", ["unfused", "fused", "chunked"])
def test_als_fit_with_kernel_matches_einsum(rng, monkeypatch, mode):
    """The whole sweep with every bucket on the (interpreted) kernel against
    the einsum pair: straight-line, inside the fused solve's `post`, and
    inside the `lax.map` chunks."""
    n_users, n_items, k = 40, 30, 4
    full = rng.normal(size=(n_users, k)) @ rng.normal(size=(n_items, k)).T
    u, i = np.nonzero(rng.uniform(size=full.shape) < 0.6)
    r = full[u, i]
    init = (rng.normal(size=(n_users, k)).astype(np.float32),
            rng.normal(size=(n_items, k)).astype(np.float32))
    cfg = A.ALSConfig(num_factors=k, iterations=2, lambda_=0.1)
    mesh = make_mesh(2)
    if mode == "fused":
        monkeypatch.setenv("FLINK_MS_ALS_FUSED", "1")
    if mode == "chunked":
        monkeypatch.setenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES", "512")
    base = A.als_fit(u, i, r, cfg, mesh, init=init)
    A._SWEEP_CACHE.clear()   # the resolver is not in the sweep's cache key
    monkeypatch.setattr(A, "resolve_assembly", _kernel_everywhere)
    try:
        kernel = A.als_fit(u, i, r, cfg, mesh, init=init)
    finally:
        A._SWEEP_CACHE.clear()
    np.testing.assert_allclose(
        kernel.user_factors, base.user_factors, rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(
        kernel.item_factors, base.item_factors, rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("platform,y_dtype,implicit,w,k,precision,want", [
    ("tpu", "float32", False, 144, 50, "highest", "kernel"),
    ("tpu", "float32", False, 97096, 50, "highest", "kernel"),
    ("tpu", "float32", False, 144, 64, "default", "kernel"),
    ("tpu", "bfloat16", False, 144, 50, "highest", "einsum"),   # bf16 exchange
    ("tpu", "float32", True, 144, 50, "highest", "einsum"),     # implicit
    ("cpu", "float32", False, 144, 50, "highest", "einsum"),    # CPU mesh
    (None, "float32", False, 144, 50, "highest", "einsum"),
    ("tpu", "float32", False, 144, 50, "high", "einsum"),       # no 3-pass
    ("tpu", "float32", False, 24, 50, "highest", "kernel"),     # every width
    ("tpu", "float32", False, 144, 65, "highest", "einsum"),    # past the rank
    ("tpu", "float32", False, 144, 200, "highest", "einsum"),   # the chip ran
])
def test_resolver_and_what_it_traces(platform, y_dtype, implicit, w, k,
                                     precision, want):
    assert A.resolve_assembly(platform, y_dtype, "float32", implicit, k,
                              precision) == want
    # ... and the bucket really traces that: a pallas_call only where the
    # resolver said kernel, the einsum pair's two dot_generals otherwise
    r = 16
    jaxpr = str(jax.make_jaxpr(
        lambda tab, idx, val: A._bucket_normal_eqs(
            tab, idx, val, implicit, 40.0, jnp.float32, precision,
            platform=platform)
    )(jnp.zeros((32, k), y_dtype), jnp.zeros((r, w), jnp.int32),
      jnp.zeros((r, w), jnp.float32)))
    assert ("pallas_call" in jaxpr) == (want == "kernel")
    if want == "einsum":
        assert jaxpr.count("dot_general") == 2
