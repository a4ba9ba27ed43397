"""The SDCA steps of a CoCoA round as one Pallas TPU kernel.

A round of the Gram engine (``ops/svm.py``, scope ``svm.steps``) runs H
serial SDCA steps on each of a device's C chains.  A step draws one row j of
its chain, picks that row's label, squared norm, dual variable and running
margin, takes the closed-form clipped hinge step ``delta`` and adds
``(σ'·delta/λn) · G[j]`` to the chain's margins.  As an XLA ``fori_loop``
over ``(C, H, H)`` that is six per-element gathers and scatters a step, 8-10
ns an element whatever they hold (0.45 ms a step over 8192 chains; PERF.md
section 5, PR 37).  The state a chain touches is small, its ``(H, H)`` Gram
block and four length-H vectors, so here it stays in VMEM for the whole
local pass:

- **chains on the lane axis**: the Gram tensor lies chain-minor,
  ``(H_rows, Hp, Cp)`` with ``Hp`` = H_rows rounded up to the 8 sublanes,
  the chain state ``(Hp, Cp)``, the hoisted draws ``(Hs, Cp)``; one grid
  step holds a block of 128 chains, HBM sees the Gram tensor once a round;
- **no gather, no scatter**: every access is a select against the draw.  The
  four picks are sublane sums of ``where(row == j, ·, 0)``, the update of α
  adds ``where(row == j, delta, 0)`` and the Gram row of every lane's own j
  is a chain of selects over the block's leading (untiled) axis, a vector
  load and a select a tile.  Selected values and sums with exact zeros: the
  kernel interpreted is bit-identical to ``chain_sdca_gram``.

The caller says where it runs, as for ``cholesky_pallas``: ``interpret=True``
off the chip.  ``ops/svm.resolve_step`` decides who takes the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# what a grid step's blocks may take of the kernel's 16 MiB of scoped VMEM,
# each held twice (the pipeline's double buffer).  One lane tile of chains a
# grid step: on the chip wider blocks lost (49-row chains: 0.39 ms a round
# at 128, 0.48 at 256, 0.85 at 512; PERF.md section 6, PR 38)
_VMEM_BUDGET = 14 << 20


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def fits_vmem(h_rows: int, steps: int) -> bool:
    """Whether a grid step's blocks (128 chains' Gram rows, four state
    vectors, Δα and draws, f32 and int32) fit the VMEM budget twice: chains
    of up to 113 rows at one local pass.  Past it the caller keeps the XLA
    step."""
    hp = _round_up(h_rows, SUBLANES)
    per_chain = 4 * (h_rows * hp + 5 * hp + _round_up(steps, SUBLANES))
    return 2 * per_chain * LANES <= _VMEM_BUDGET


def _sdca_kernel(j_ref, g_ref, wx_ref, y_ref, q_ref, a_ref, da_ref, *,
                 steps: int, lam_n: float, sigma_p: float):
    """One block of 128 chains: draws (Hs, 128), Gram rows (H_rows, Hp, 128),
    margins, labels, squared norms and α (Hp, 128) -> Δα (Hp, 128).  The
    pass's carries (margins and α, Hp / 8 vector registers each) and the
    Gram row being selected stay in registers."""
    h_rows, hp, _ = g_ref.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (hp, LANES), 0)
    tiles_at = range(0, hp, SUBLANES)

    def step(h, carry):
        wx, a = carry
        # row h of the draws, (1, 128): Mosaic loads a dynamic row only at
        # a multiple of 8, so the tile around it, and a select
        tile = j_ref[pl.ds(pl.multiple_of(h // SUBLANES * SUBLANES,
                                          SUBLANES), SUBLANES), :]
        j = jnp.sum(jnp.where(row[:SUBLANES] == h % SUBLANES, tile, 0),
                    axis=0, keepdims=True)
        hit = row == j

        def pick(v):
            return jnp.sum(jnp.where(hit, v, 0.0), axis=0, keepdims=True)

        y, qii, a_j = pick(y_ref[:]), pick(q_ref[:]), pick(a)
        grad = 1.0 - y * pick(wx)
        # the dual step of ops/svm.chain_sdca_gram, letter for letter
        new_dual = jnp.clip(
            a_j * y + grad * lam_n / (sigma_p * jnp.maximum(qii, 1e-12)),
            0.0, 1.0,
        )
        delta = jnp.where(qii > 0, y * new_dual - a_j, 0.0)
        a = a + jnp.where(hit, delta, 0.0)
        # G[j] of every lane's own j: one compare a Gram row, then a load
        # and a select a tile of it (the loop is traced once and unrolled)
        j8 = jnp.broadcast_to(j, (SUBLANES, LANES))

        def select_row(r, tiles):
            mine = j8 == r
            return tuple(jnp.where(mine, g_ref[r, pl.ds(t, SUBLANES), :], g)
                         for t, g in zip(tiles_at, tiles))

        tiles = jax.lax.fori_loop(
            1, h_rows, select_row,
            tuple(g_ref[0, pl.ds(t, SUBLANES), :] for t in tiles_at),
            unroll=True)
        wx = wx + (sigma_p * delta / lam_n) * jnp.concatenate(tiles, axis=0)
        return wx, a

    a0 = a_ref[:]
    _, a = jax.lax.fori_loop(0, steps, step, (wx_ref[:], a0))
    da_ref[:] = a - a0


@functools.partial(jax.jit, static_argnames=(
    "steps", "lam_n", "sigma_p", "interpret"))
def sdca_steps_lanes(j_all, gram_t, wx0_t, label_t, sqn_t, alpha_t, *,
                     steps: int, lam_n: float, sigma_p: float,
                     interpret: bool):
    """``steps`` SDCA steps of Cp chains that lie chain-minor -> Δα (Hp, Cp).

    ``j_all`` (Hs, Cp) int32, Hs = ``steps`` rounded up to 8: step h of
    chain c updates row ``j_all[h, c]``; ``gram_t`` (H_rows, Hp, Cp):
    ``gram_t[j, i, c]`` = x_j · x_i of chain c; ``wx0_t``, ``label_t``,
    ``sqn_t``, ``alpha_t`` (Hp, Cp): round-start margins, labels, squared
    norms, dual variables.  Cp is a multiple of 128.  Pad rows (Hp past
    H_rows, a chain's rows past its examples) and pad chains carry a squared
    norm of 0, for which ``delta`` is 0, and no draw names a row past
    H_rows."""
    hs, cp = j_all.shape
    h_rows, hp, _ = gram_t.shape
    state = pl.BlockSpec((hp, LANES), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_sdca_kernel, steps=steps, lam_n=lam_n,
                          sigma_p=sigma_p),
        grid=(cp // LANES,),
        in_specs=[pl.BlockSpec((hs, LANES), lambda i: (0, i)),
                  pl.BlockSpec((h_rows, hp, LANES), lambda i: (0, 0, i)),
                  state, state, state, state],
        out_specs=state,
        out_shape=jax.ShapeDtypeStruct((hp, cp), alpha_t.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(j_all, gram_t, wx0_t, label_t, sqn_t, alpha_t)
