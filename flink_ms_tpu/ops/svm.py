"""CoCoA distributed linear SVM on a TPU device mesh.

TPU-native re-design of the capability behind ``SVM().fit(trainingDS)``
(reference call site ``flink-svm/.../SVMImpl.scala:24-29``; solver semantics
are FlinkML's CoCoA + local SDCA [dep], SURVEY.md §2.2):

    min_w  (λ/2)||w||² + (1/n) Σ_j max(0, 1 − y_j w·x_j)

Data is split into ``Blocks`` = K *logical* blocks (``setBlocks``,
SVMImpl.scala:25) laid out as K independent SDCA chains over a D-device
mesh — K may exceed D, in which case C = ceil(K/D) chains are stacked per
device and run under ``vmap``: every ``fori_loop`` step advances C chains
at once (a (C, L) gather/scatter instead of one row), so the serial depth
per round is rows-per-chain, not rows-per-device.  That is the TPU answer
to the reference's one-chain-per-TaskManager layout: more blocks = shorter
chains = more hardware parallelism, with the classic CoCoA convergence
story governing the block count.

Each chain runs H local SDCA steps (closed-form hinge dual update of
Shalev-Shwartz & Zhang) against a chain-local copy of the weight vector;
chains exchange through a single ``psum`` over ICI per outer round.  Two
combination modes:

- ``mode="avg"`` (default; FlinkML/CoCoA-v1 parity, Jaggi et al. 2014):
  block deltas are *averaged*, w += (β/K)·ΣΔw_k with β = stepsize, and the
  local subproblem is unscaled (σ′ = 1).
- ``mode="add"`` (CoCoA+, Ma, Smith, Jaggi et al. 2015 "Adding vs.
  Averaging in Distributed Primal-Dual Optimization"): block deltas are
  *added*, w += γ·ΣΔw_k with γ = stepsize, and each local subproblem is
  smoothed by σ′ = γ·K (the safe choice) — both the dual step denominator
  and the chain-local w view carry σ′.  At large K (the TPU-friendly
  regime) "add" keeps full per-round progress where averaging dilutes it
  by 1/K.

The whole fit is one XLA program with a *dynamic* outer-round count
(``fori_loop`` with a traced bound), so one compiled executable serves any
``--iteration`` value — benchmarks time extra rounds without recompiling.

Surfaced knobs follow FlinkML's parameter set: Blocks, Iterations,
LocalIterations, Regularization, Stepsize, Seed [dep]; ThresholdValue /
OutputDecisionFunction live client-side (SVMPredict.java:33-34,80-86).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.formats import SparseData
from ..obs import metrics as obs_metrics
from ..obs import tracing
from ..parallel.mesh import BLOCK_AXIS, block_sharding, num_blocks


@dataclasses.dataclass(frozen=True)
class SVMConfig:
    iterations: int = 10          # outer CoCoA rounds (SVMImpl --iteration)
    local_iterations: int = 10    # SDCA steps per block per round [dep default]
    regularization: float = 1.0   # λ [dep default]
    stepsize: float = 1.0         # β (avg) / γ (add) scaling of the update
    seed: int = 0
    mode: str = "avg"             # "avg" = CoCoA-v1 parity, "add" = CoCoA+
    # local-subproblem smoothing σ' for mode="add" (CoCoA+).  None = the
    # provably safe γ·K.  Values in [1, γK) are the aggressive regime:
    # valid when blocks' updates rarely collide (sparse data, e.g. RCV1);
    # the fit stays convergent in practice and each round makes up to
    # γK/σ' times more progress.  Ignored in avg mode.
    sigma_prime: Optional[float] = None
    dtype: jnp.dtype = jnp.float32
    # Inner-loop engine.  "scatter": every SDCA step gathers/scatters a
    # chain-local copy of the (d,)-dim weight vector — O(L) work per step
    # but random access into (C, d) state.  "gram": precompute each chain's
    # (H, H) row-Gram matrix once (densify-matmul on the MXU), keep a
    # running margin vector wx[i] = w_loc·x_i, and make every step a dense
    # (C, H) AXPY — the weight vector is touched once per ROUND (one
    # gather for wx0, one scatter for X^T dalpha) instead of once per
    # step.  Same update sequence (same RNG, same closed-form dual step),
    # reassociated arithmetic.  "auto": gram when the (C, H, H) tensor
    # fits FLINK_MS_SVM_GRAM_BYTES (default 1 GiB per device).
    inner: str = "auto"

    def __post_init__(self):
        if self.mode not in ("avg", "add"):
            raise ValueError("mode must be avg or add")
        if self.sigma_prime is not None and self.sigma_prime < 1.0:
            raise ValueError("sigma_prime must be >= 1")
        if self.inner not in ("auto", "gram", "scatter"):
            raise ValueError("inner must be auto|gram|scatter")


@dataclasses.dataclass
class SVMModel:
    weights: np.ndarray  # (n_features,) dense primal vector

    def decision_function(self, data: SparseData) -> np.ndarray:
        if data.n_examples == 0:
            return np.zeros(0)
        contrib = data.values * self.weights[data.indices]
        # reduceat over CSR row starts; empty rows need explicit zeroing
        # (reduceat on an empty segment returns the next element)
        sums = np.zeros(data.n_examples)
        starts = data.indptr[:-1]
        nonempty = data.indptr[1:] > starts
        if contrib.size:
            red = np.add.reduceat(contrib, np.minimum(starts, contrib.size - 1))
            sums[nonempty] = red[nonempty]
        return sums

    def hinge_loss(self, data: SparseData, lambda_: float) -> float:
        margins = data.labels * self.decision_function(data)
        return float(
            np.mean(np.maximum(0.0, 1.0 - margins))
            + 0.5 * lambda_ * float(self.weights @ self.weights)
        )


# ---------------------------------------------------------------------------
# host-side layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BlockedSVMProblem:
    """Examples split into K logical blocks with per-row padded sparse
    storage (K = the reference's ``setBlocks``; independent of the device
    count — the kernel stacks ceil(K/D) blocks per device).

    Padding rows have label 0 and empty features; the SDCA step masks them
    (zero row norm => zero update), so they never affect the solution.
    """

    n_blocks: int
    n_examples: int      # real examples (pre-padding)
    n_features: int
    rows_per_block: int
    idx: np.ndarray      # (K, rows_pb, L) int32 feature indices (0-based)
    val: np.ndarray      # (K, rows_pb, L) values, 0 where padded
    label: np.ndarray    # (K, rows_pb) +-1, 0 for padding rows
    sq_norm: np.ndarray  # (K, rows_pb) ||x_j||^2


def prepare_svm_blocked(
    data: SparseData, n_blocks: int, seed: int = 0, dtype=np.float32
) -> BlockedSVMProblem:
    """Vectorized re-layout: shuffle examples across K blocks, pad each row
    to the max nnz (static shapes for XLA)."""
    with tracing.stage("svm.prepare"):
        n = data.n_examples
        rows_pb = -(-n // n_blocks) if n else 1
        lens = (data.indptr[1:] - data.indptr[:-1]).astype(np.int64)
        L = max(int(lens.max()) if n else 1, 1)

        # padded row-major staging in original example order
        mask = np.arange(L)[None, :] < lens[:, None]           # (n, L)
        idx_rows = np.zeros((n, L), dtype=np.int32)
        val_rows = np.zeros((n, L), dtype=dtype)
        idx_rows[mask] = data.indices                          # CSR order
        val_rows[mask] = data.values.astype(dtype)

        # slot s <- example order[s]
        order = np.random.default_rng(seed).permutation(n)
        idx = np.zeros((n_blocks * rows_pb, L), dtype=np.int32)
        val = np.zeros((n_blocks * rows_pb, L), dtype=dtype)
        label = np.zeros((n_blocks * rows_pb,), dtype=dtype)
        idx[:n] = idx_rows[order]
        val[:n] = val_rows[order]
        signs = np.sign(data.labels[order]).astype(dtype)
        label[:n] = np.where(signs == 0, 1.0, signs)  # labels must be +-1
        sq_norm = np.sum(val.astype(np.float64) ** 2, axis=-1).astype(dtype)
        # slot s -> (block s // rows_pb, row s % rows_pb): contiguous rows per
        # block, matching the reference's partition-then-iterate layout
        return BlockedSVMProblem(
            n_blocks=n_blocks,
            n_examples=n,
            n_features=data.n_features,
            rows_per_block=rows_pb,
            idx=idx.reshape(n_blocks, rows_pb, L),
            val=val.reshape(n_blocks, rows_pb, L),
            label=label.reshape(n_blocks, rows_pb),
            sq_norm=sq_norm.reshape(n_blocks, rows_pb),
        )


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _dw_choice() -> str:
    """FLINK_MS_SVM_DW: how the Gram engine applies the round-end
    Δw = Xᵀ Δα update.  "direct": one unsorted scatter-add over all
    (C·H·L) entries.  "sorted": gather the row-major contribution array
    through a precomputed feature-sorted permutation, then a sorted
    segment-sum.  "presorted": store val ALREADY feature-sorted at prepare
    time, so the round end multiplies the streamed sorted values by a
    gather from only the tiny (C·H) Δα table and segment-sums — no
    runtime permutation of the big array.  "auto" (default) = direct
    everywhere.  Measured on one TPU v5e in the benchmark cell
    ``rcv1-cocoa.cocoa-rounds`` (PERF.md §5; 8192 chains x 83 rows padded
    to 256, 174M entries of which 49.6M are real): the direct scatter-add
    takes 1.42 s of a 2.78 s round (8.2 ns an entry, pads included) and
    the round-start gather ``take(w, idx)`` 1.31 s; the steps between them
    0.04 s.  "sorted" and "presorted" have no reading on that cell: it is
    where they are to be judged (ROADMAP S5, D5)."""
    choice = os.environ.get("FLINK_MS_SVM_DW", "auto")
    if choice not in ("auto", "direct", "sorted", "presorted"):
        # a typo'd knob must not silently fall through to the direct
        # scatter — A/B verdicts depend on the requested path running
        raise ValueError(
            f"FLINK_MS_SVM_DW={choice!r} must be "
            "auto|direct|sorted|presorted"
        )
    if choice == "auto":
        return "direct"
    return choice


def _step_choice() -> str:
    """FLINK_MS_SVM_STEP: how the Gram engine's SDCA step touches chain
    state.  "dynamic": per-chain dynamic gather of the Gram row + scatter-
    add into alpha — O(1) memory touched per step, with a threefry draw
    inside the fori_loop.  "onehot": hoist the (C, H) step-index draw out
    of the loop and express every read/write as a dense mask/one-hot
    contraction — pure VPU/MXU work, bit-identical results (products are
    exact 0s and 1s).  "auto" = dynamic everywhere.  Measured on one TPU
    v5e in ``rcv1-cocoa.cocoa-rounds`` (PERF.md §5): the 83 dynamic steps
    of 8192 chains take 37 ms of a 2.78 s round, 0.45 ms a step; the
    round's boundary (gather and scatter-add, see _dw_choice) is the other
    98.5%.  "onehot" has no reading on that cell; it stays selectable for
    meshes where the boundary shrinks (ROADMAP D5)."""
    choice = os.environ.get("FLINK_MS_SVM_STEP", "auto")
    if choice not in ("auto", "dynamic", "onehot"):
        # as _dw_choice: a typo must not run the dynamic step in silence
        raise ValueError(
            f"FLINK_MS_SVM_STEP={choice!r} must be auto|dynamic|onehot"
        )
    if choice == "auto":
        return "dynamic"
    return choice


def _resolve_inner(problem: BlockedSVMProblem, config: SVMConfig,
                   mesh: Mesh) -> str:
    """auto -> gram|scatter, from the per-device (C, H, H) Gram budget
    (FLINK_MS_SVM_GRAM_BYTES, default 1 GiB).  Resolved BEFORE the fit
    cache key is built, so the env var keys the executable exactly when it
    can affect it."""
    if config.inner != "auto":
        return config.inner
    D = num_blocks(mesh)
    C = _round_up(problem.n_blocks, D) // D
    H = problem.rows_per_block
    gram_bytes = C * H * H * np.dtype(config.dtype).itemsize
    limit = int(os.environ.get("FLINK_MS_SVM_GRAM_BYTES", 1 << 30))
    return "gram" if gram_bytes <= limit else "scatter"


# ---------------------------------------------------------------------------
# device-side kernel
# ---------------------------------------------------------------------------

def _make_fit(problem: BlockedSVMProblem, config: SVMConfig, mesh: Mesh):
    D = num_blocks(mesh)
    K = problem.n_blocks               # real logical blocks
    C = _round_up(K, D) // D           # chains stacked per device
    n = problem.n_examples
    lam = config.regularization
    H = config.local_iterations
    lam_n = lam * max(n, 1)
    dtype = config.dtype
    if config.mode == "avg":
        gamma = config.stepsize / K    # averaged combination (CoCoA-v1)
        sigma_p = 1.0
    else:
        gamma = config.stepsize        # added combination (CoCoA+)
        sigma_p = (                    # safe default σ' = γK
            config.sigma_prime if config.sigma_prime is not None
            else config.stepsize * K
        )

    H_rows = problem.rows_per_block
    d = problem.n_features
    inner = _resolve_inner(problem, config, mesh)
    step_mode = _step_choice()
    dw_mode = _dw_choice() if inner == "gram" else "direct"

    def chain_sdca(w, idx_c, val_c, label_c, sqn_c, alpha_c, key_c):
        """H serial SDCA steps of ONE chain; vmapped over the C chains of a
        device so every step is a (C, L)-wide gather/compute/scatter."""
        rows = label_c.shape[0]

        def sdca_step(h, inner):
            w_loc, a = inner
            j = jax.random.randint(jax.random.fold_in(key_c, h), (), 0, rows)
            ids = idx_c[j]
            x = val_c[j]
            y = label_c[j]
            qii = sqn_c[j]
            wx = jnp.sum(jnp.take(w_loc, ids) * x)
            grad = 1.0 - y * wx
            # closed-form hinge dual step on the σ'-smoothed local
            # subproblem, clipped to the box [0, 1]
            a_j = a[j]
            new_dual = jnp.clip(
                a_j * y + grad * lam_n / (sigma_p * jnp.maximum(qii, 1e-12)),
                0.0, 1.0,
            )
            delta = jnp.where(qii > 0, y * new_dual - a_j, 0.0)
            a = a.at[j].add(delta)
            # the chain-local view carries σ' (CoCoA+ models the quadratic
            # coupling of its OWN updates σ'-fold, so later coordinates in
            # the chain see the smoothed effect); σ' = 1 in avg mode
            w_loc = w_loc.at[ids].add(sigma_p * delta * x / lam_n)
            return w_loc, a

        w_loc, a = jax.lax.fori_loop(0, H, sdca_step, (w, alpha_c))
        # Δw of this chain under the TRUE coupling: (w_loc − w)/σ'
        return (w_loc - w) / sigma_p, a - alpha_c

    def chain_sdca_gram(wx0, gram_c, label_c, sqn_c, alpha_c, key_c):
        """H serial SDCA steps of ONE chain, Gram-matrix inner loop: the
        running margin vector wx[i] = w_loc·x_i absorbs each update via
        one Gram row (wx += σ'·Δα_j/λn · G[j, :]), so no step touches the
        (d,)-dim weights.  Same RNG and dual step as ``chain_sdca`` —
        identical update sequence, reassociated arithmetic."""
        def sdca_step(h, inner_c):
            wx, a = inner_c
            j = jax.random.randint(jax.random.fold_in(key_c, h), (), 0,
                                   label_c.shape[0])
            y = label_c[j]
            qii = sqn_c[j]
            a_j = a[j]
            grad = 1.0 - y * wx[j]
            new_dual = jnp.clip(
                a_j * y + grad * lam_n / (sigma_p * jnp.maximum(qii, 1e-12)),
                0.0, 1.0,
            )
            delta = jnp.where(qii > 0, y * new_dual - a_j, 0.0)
            a = a.at[j].add(delta)
            wx = wx + (sigma_p * delta / lam_n) * gram_c[j]
            return wx, a

        _, a = jax.lax.fori_loop(0, H, sdca_step, (wx0, alpha_c))
        return a - alpha_c

    def chain_sdca_gram_onehot(wx0, gram_c, label_c, sqn_c, alpha_c, key_c):
        """``chain_sdca_gram`` with every dynamic access rewritten as a
        dense one-hot contraction and the per-step RNG hoisted out of the
        loop: no gather, no scatter, no threefry inside the fori_loop.
        Bit-identical to the dynamic path — the index draw is the same
        fold_in(key, h) sequence (vectorized), and one-hot reads/writes
        multiply by exact 1.0/0.0 so no value is ever rounded
        (``precision="highest"`` keeps the Gram-row contraction in f32)."""
        rows = label_c.shape[0]
        j_all = jax.vmap(
            lambda h: jax.random.randint(
                jax.random.fold_in(key_c, h), (), 0, rows
            )
        )(jnp.arange(H))
        iota = jnp.arange(rows)

        def sdca_step(h, inner_c):
            wx, a = inner_c
            onehot = (iota == j_all[h]).astype(dtype)      # (rows,)
            y = jnp.sum(label_c * onehot)
            qii = jnp.sum(sqn_c * onehot)
            a_j = jnp.sum(a * onehot)
            grad = 1.0 - y * jnp.sum(wx * onehot)
            new_dual = jnp.clip(
                a_j * y + grad * lam_n / (sigma_p * jnp.maximum(qii, 1e-12)),
                0.0, 1.0,
            )
            delta = jnp.where(qii > 0, y * new_dual - a_j, 0.0)
            a = a + delta * onehot
            grow = jnp.einsum("r,rk->k", onehot, gram_c,
                              precision="highest",
                              preferred_element_type=dtype)
            wx = wx + (sigma_p * delta / lam_n) * grow
            return wx, a

        _, a = jax.lax.fori_loop(0, H, sdca_step, (wx0, alpha_c))
        return a - alpha_c

    sdca_gram = (chain_sdca_gram_onehot if step_mode == "onehot"
                 else chain_sdca_gram)

    def build_gram(idx_s, val_s):
        """Per-chain row-Gram G[c] = S_c S_cᵀ via densify-matmul: scatter
        one chain's L-padded sparse rows into an (H, d) dense staging
        buffer and take the (H, H) product on the MXU.  lax.map chunking
        bounds the staging transient; pad rows/slots have val 0 and
        contribute nothing.  One-time cost per fit call."""
        rows_ar = jnp.arange(H_rows)
        B = max(int(
            (256 << 20) // max(H_rows * d * np.dtype(dtype).itemsize, 1)
        ), 1)

        def one(args):
            idx_c, val_c = args
            dense = jnp.zeros((H_rows, d), dtype).at[
                rows_ar[:, None], idx_c
            ].add(val_c)
            return jnp.einsum("id,jd->ij", dense, dense,
                              precision="highest",
                              preferred_element_type=dtype)

        with jax.named_scope("svm.gram"):
            return jax.lax.map(one, (idx_s, val_s), batch_size=B)

    def block_fit(span, w0, idx, val, label, sq_norm, alpha0, seed_arr,
                  gram=None, dw_a=None, dw_b=None, dw_c=None):
        # dw_* operands depend on dw_mode: sorted -> (perm, ids), presorted
        # -> (val_sorted, ids, src_row); unused modes pass nothing
        # span = [start, stop): rounds run with ABSOLUTE indices so the
        # per-round RNG (fold_in of the round number) is identical whether
        # the caller runs one long fit or chains warm-started segments
        # per-device shards: idx (C, rows, L), alpha (C, rows); w0 replicated
        device_id = jax.lax.axis_index(BLOCK_AXIS)

        def chain_keys(it):
            # chain RNG: globally unique (seed, global chain id, round)
            chain_ids = device_id * C + jnp.arange(C)
            return jax.vmap(
                lambda c: jax.random.fold_in(
                    jax.random.fold_in(
                        jax.random.PRNGKey(seed_arr[0]), c
                    ),
                    it,
                )
            )(chain_ids)

        # The round's parts carry named scopes (svm.margins, svm.steps,
        # svm.dw, svm.combine) so that a profile splits a round by them;
        # the scatter engine computes its margins inside each step, so its
        # rounds have no svm.margins.
        def outer(it, carry):
            w, alpha = carry
            with jax.named_scope("svm.steps"):
                keys = chain_keys(it)
                dw, dalpha = jax.vmap(
                    chain_sdca, in_axes=(None, 0, 0, 0, 0, 0, 0)
                )(w, idx, val, label, sq_norm, alpha, keys)
            with jax.named_scope("svm.dw"):
                dw = jnp.sum(dw, axis=0)
            with jax.named_scope("svm.combine"):
                w = w + gamma * jax.lax.psum(dw, BLOCK_AXIS)
                alpha = alpha + gamma * dalpha
            return w, alpha

        def outer_gram(it, carry):
            w, alpha = carry
            # round-start margins for every row: ONE (C, H, L) gather of w
            # HIGHEST: the scatter path computes these margins as full-f32
            # elementwise work; a default-precision (bf16-pass) contraction
            # here would seed every SDCA step with ~1e-3 relative error and
            # break the documented cross-engine equivalence on TPU.
            with jax.named_scope("svm.margins"):
                wx0 = jnp.einsum("chl,chl->ch", jnp.take(w, idx, axis=0),
                                 val, precision="highest",
                                 preferred_element_type=dtype)
            with jax.named_scope("svm.steps"):
                keys = chain_keys(it)
                dalpha = jax.vmap(sdca_gram)(
                    wx0, gram, label, sq_norm, alpha, keys
                )
            # this device's Δw = Σ_chains X_cᵀ Δα_c / λn: ONE reduction
            # per round (the scatter engine pays one per STEP per chain).
            # Mode trade-offs in _dw_choice's docstring.
            with jax.named_scope("svm.dw"):
                if dw_mode == "presorted":
                    # val is stored feature-sorted (dw_a) at prepare time,
                    # so the only runtime gather reads the tiny (C·H) Δα
                    # table
                    dw = jax.ops.segment_sum(
                        dw_a[0] * dalpha.reshape(-1)[dw_c[0]], dw_b[0],
                        num_segments=d, indices_are_sorted=True,
                    ) / lam_n
                elif dw_mode == "sorted":
                    contrib = (val * dalpha[:, :, None]).reshape(-1)
                    dw = jax.ops.segment_sum(
                        contrib[dw_a[0]], dw_b[0], num_segments=d,
                        indices_are_sorted=True,
                    ) / lam_n
                else:
                    contrib = (val * dalpha[:, :, None]).reshape(-1)
                    dw = jnp.zeros((d,), dtype).at[idx.reshape(-1)].add(
                        contrib
                    ) / lam_n
            with jax.named_scope("svm.combine"):
                w = w + gamma * jax.lax.psum(dw, BLOCK_AXIS)
                alpha = alpha + gamma * dalpha
            return w, alpha

        body = outer_gram if inner == "gram" else outer
        return jax.lax.fori_loop(span[0], span[1], body, (w0, alpha0))

    spec3 = P(BLOCK_AXIS, None, None)
    spec2 = P(BLOCK_AXIS, None)
    in_specs = (P(), P(), spec3, spec3, spec2, spec2, spec2, P())
    if inner == "gram":
        in_specs = in_specs + (spec3,)
        if dw_mode == "sorted":
            in_specs = in_specs + (spec2, spec2)
        elif dw_mode == "presorted":
            in_specs = in_specs + (spec2, spec2, spec2)
    jfit = jax.jit(shard_map(
        block_fit,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(), spec2),
        check_vma=False,
    ))

    def fit(rounds, *args, start=0):
        """``fit(rounds, *dev_args)`` runs rounds from scratch; pass
        ``start=r0`` (with the w/alpha carried out of a previous segment
        as args[0]/args[5]) to continue EXACTLY where a prior call
        stopped — absolute-round RNG makes chained segments bit-identical
        to one long fit."""
        # the stage is the host's side of the call: the rounds are
        # enqueued, not awaited (their device time lies under the svm.*
        # scopes of the same profile)
        with tracing.stage("svm.fit"):
            lo = jnp.asarray(start, jnp.int32)
            span = jnp.stack([lo, lo + jnp.asarray(rounds, jnp.int32)])
            out = jfit(span, *args)
        obs_metrics.get_registry().counter("tpums_svm_rounds_total").inc(
            int(rounds))
        return out
    # the Gram build is hoisted out of the fit: compile_svm_fit runs it
    # once and ships the (Kp, H, H) tensor as a device arg, so repeat fit
    # calls (benchmark loops, retrain cycles) don't pay it again
    gram_fn = None
    if inner == "gram":
        gram_fn = jax.jit(shard_map(
            build_gram, mesh=mesh,
            in_specs=(spec3, spec3), out_specs=spec3, check_vma=False,
        ))
    return fit, gram_fn, dw_mode if inner == "gram" else "direct"


_FIT_CACHE: "dict" = {}
_FIT_CACHE_MAX = 8


def _cached_fit(problem: BlockedSVMProblem, config: SVMConfig, mesh: Mesh):
    """One compiled program per (layout shapes, config-sans-iterations,
    mesh): repeat fits and benchmark loops skip retracing; the round count
    is a traced argument."""
    key = (
        mesh,
        problem.n_blocks,
        problem.rows_per_block,
        problem.idx.shape,
        problem.n_features,
        problem.n_examples,  # lam_n = lam * n is baked into the program
        config.local_iterations,
        config.regularization,
        config.stepsize,
        config.mode,
        config.sigma_prime,
        str(config.dtype),
        _resolve_inner(problem, config, mesh),
        _dw_choice(),
        _step_choice(),
    )
    fn = _FIT_CACHE.pop(key, None)
    if fn is None:
        fn = _make_fit(problem, config, mesh)
    _FIT_CACHE[key] = fn  # re-insert: dict order gives LRU eviction
    while len(_FIT_CACHE) > _FIT_CACHE_MAX:
        del _FIT_CACHE[next(iter(_FIT_CACHE))]
    return fn


def _set_layout_gauges(problem: BlockedSVMProblem, Kp: int, D: int,
                       gram_bytes: int) -> None:
    """What the installed layout holds, for whoever reads the registry:
    row slots (pad rows and pad blocks included), the width every row is
    padded to, the entries of the padded arrays that carry no value, the
    Gram tensor's bytes (0 on the scatter engine), chains per device."""
    reg = obs_metrics.get_registry()
    slots = Kp * problem.rows_per_block
    width = problem.idx.shape[-1]
    reg.gauge("tpums_svm_rows").set(slots)
    reg.gauge("tpums_svm_row_width").set(width)
    reg.gauge("tpums_svm_pad_entries").set(
        slots * width - int(np.count_nonzero(problem.val)))
    reg.gauge("tpums_svm_gram_bytes").set(gram_bytes)
    reg.gauge("tpums_svm_chains_per_device").set(Kp // D)


def compile_svm_fit(
    problem: BlockedSVMProblem, config: SVMConfig, mesh: Mesh
):
    """-> (fit_fn, dev_args): the compiled CoCoA program plus device-
    resident sharded inputs.  ``fit_fn(iterations, *dev_args)`` -> (w,
    alpha shards).  Benchmarks call ``fit_fn`` directly so host<->device
    transfer and compile stay out of the timed region."""
    D = num_blocks(mesh)
    K = problem.n_blocks
    Kp = _round_up(K, D)  # pad with empty blocks so K shards evenly; empty
    # chains produce zero deltas and the combination scale uses the real K
    dtype = config.dtype

    def pad_blocks(a):
        if Kp == K:
            return a
        widths = [(0, Kp - K)] + [(0, 0)] * (a.ndim - 1)
        return np.pad(a, widths)

    w0 = jnp.zeros((problem.n_features,), dtype=dtype)
    alpha0 = jnp.zeros((Kp, problem.rows_per_block), dtype=dtype)
    shard3 = block_sharding(mesh, rank=3)
    shard2 = block_sharding(mesh, rank=2)
    rep = NamedSharding(mesh, P())
    # the two stages end when the device has what they made, so that a
    # profile shows the transfer and the Gram build, not their dispatch
    with tracing.stage("svm.place"):
        dev_args = jax.block_until_ready([
            jax.device_put(w0, rep),
            jax.device_put(jnp.asarray(pad_blocks(problem.idx)), shard3),
            jax.device_put(
                jnp.asarray(pad_blocks(problem.val).astype(dtype)), shard3
            ),
            jax.device_put(
                jnp.asarray(pad_blocks(problem.label).astype(dtype)), shard2
            ),
            jax.device_put(
                jnp.asarray(pad_blocks(problem.sq_norm).astype(dtype)),
                shard2,
            ),
            jax.device_put(alpha0, shard2),
            jax.device_put(
                jnp.asarray([config.seed], dtype=jnp.uint32), rep
            ),
        ])
    fit, gram_fn, dw_mode = _cached_fit(problem, config, mesh)
    if gram_fn is not None:
        with tracing.stage("svm.gram_build"):
            dev_args.append(jax.block_until_ready(
                gram_fn(dev_args[1], dev_args[2])
            ))
    _set_layout_gauges(problem, Kp, D, dev_args[7].nbytes if gram_fn else 0)
    if dw_mode in ("sorted", "presorted"):
        # per-device feature-sorted layout of the flattened (C, H, L)
        # entries (host-side, once per layout).  sorted ships (perm, ids):
        # the round end gathers the big contribution array through perm.
        # presorted ships (val_sorted, ids, src_row): values are stored
        # already sorted, so the round end's only gather is src_row into
        # the (C·H) Δα table.
        idx_p = pad_blocks(problem.idx)
        L = idx_p.shape[-1]
        Cd = Kp // D
        M = Cd * problem.rows_per_block * L
        ids = np.empty((D, M), np.int32)
        if dw_mode == "sorted":
            perm = np.empty((D, M), np.int32)
        else:
            val_p = pad_blocks(problem.val)
            val_s = np.empty((D, M), np.dtype(dtype))
            src = np.empty((D, M), np.int32)
        for dd in range(D):
            flat = idx_p[dd * Cd:(dd + 1) * Cd].reshape(-1)
            order = np.argsort(flat, kind="stable").astype(np.int32)
            ids[dd] = flat[order]
            if dw_mode == "sorted":
                perm[dd] = order
            else:
                val_s[dd] = val_p[dd * Cd:(dd + 1) * Cd].reshape(-1)[order]
                src[dd] = order // L  # device-local flat (C·H) row index
        if dw_mode == "sorted":
            dev_args.append(jax.device_put(jnp.asarray(perm), shard2))
            dev_args.append(jax.device_put(jnp.asarray(ids), shard2))
        else:
            dev_args.append(jax.device_put(jnp.asarray(val_s), shard2))
            dev_args.append(jax.device_put(jnp.asarray(ids), shard2))
            dev_args.append(jax.device_put(jnp.asarray(src), shard2))
    return fit, dev_args


def svm_fit(
    data: SparseData,
    config: SVMConfig,
    mesh: Mesh,
    problem: Optional[BlockedSVMProblem] = None,
) -> SVMModel:
    """Train the CoCoA linear SVM; returns the dense primal weight vector
    (the reference's ``weightsOption: DataSet[DenseVector]``,
    SVMImpl.scala:31-35)."""
    D = num_blocks(mesh)
    if problem is None:
        problem = prepare_svm_blocked(data, D, seed=config.seed)
    fit, dev_args = compile_svm_fit(problem, config, mesh)
    w, _alpha = fit(config.iterations, *dev_args)
    from ..parallel.distributed import to_host_array

    return SVMModel(weights=to_host_array(w).astype(np.float64))
