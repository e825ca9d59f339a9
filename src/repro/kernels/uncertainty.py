"""Pallas TPU fused uncertainty scorer — CLAMShell's decision-latency hot spot.

Point selection (paper §5.1/5.3) scores every candidate's predictive entropy.
Done naively that materializes softmax over the full vocab/class dim in HBM
(the paper's corpora are small; a 2026 deployment scores 10^6+ candidates over
10^5+ classes). This kernel streams (block_n x block_v) logit tiles through
VMEM keeping three running statistics per row — max m, partition Z, and
sum_i e^{l_i - m} l_i — and emits entropy H = m + log Z - S1/Z at the last
tile. Softmax never touches HBM; traffic is one read of the logits plus a
(block_n, 128) output tile per row block.

The per-row statistics and the output are 2-D and lane-replicated
(``(block_n, LANES)``): Mosaic refuses 1-D ``(block_n,)`` blocks, whose
tiling differs from the layout XLA gives a 1-D array.

Grid: (n_row_blocks, n_vocab_blocks), vocab innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _entropy_kernel(x_ref, o_ref, m_scr, z_scr, s1_scr, *, n_v, v_total,
                    block_v):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        z_scr[...] = jnp.zeros_like(z_scr)
        s1_scr[...] = jnp.zeros_like(s1_scr)

    x = x_ref[...].astype(jnp.float32)                 # (block_n, block_v)
    col = iv * block_v + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(col < v_total, x, NEG_INF)           # padded tail

    m_prev = m_scr[...]                                # (block_n, LANES)
    m_new = jnp.maximum(m_prev, x.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(x - m_new[:, :1])
    p = jnp.where(col < v_total, p, 0.0)
    z_scr[...] = z_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    s1_scr[...] = s1_scr[...] * alpha + (p * x).sum(axis=1, keepdims=True)
    m_scr[...] = m_new

    @pl.when(iv == n_v - 1)
    def _fin():
        z = jnp.maximum(z_scr[...], 1e-30)
        o_ref[...] = (m_scr[...] + jnp.log(z) - s1_scr[...] / z
                      ).astype(o_ref.dtype)


def entropy_scores(logits, *, block_n=256, block_v=512, interpret=False):
    """logits: (N, V) -> per-row predictive entropy (N,) float32."""
    N, V = logits.shape
    pn, pv = (-N) % block_n, (-V) % block_v
    if pn or pv:
        logits = jnp.pad(logits, ((0, pn), (0, pv)))
    Np, Vp = logits.shape
    n_v = Vp // block_v

    out = pl.pallas_call(
        functools.partial(_entropy_kernel, n_v=n_v, v_total=V,
                          block_v=block_v),
        grid=(Np // block_n, n_v),
        in_specs=[pl.BlockSpec((block_n, block_v), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_n, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_n, LANES), jnp.float32)] * 3,
        interpret=interpret,
    )(logits)
    return out[:N, 0]
