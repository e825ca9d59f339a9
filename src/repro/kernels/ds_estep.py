"""Pallas TPU fused Dawid-Skene E-step — labelstream's aggregation hot spot.

The E-step of Dawid-Skene EM scores every task's log-posterior over true
classes by summing, per vote, the voter's log-confusion row for the label it
gave, then softmax-normalizes. Done naively that is a (T, V, C) gather
materialized in HBM plus a separate softmax pass (T tasks, V votes/task,
C classes; a 2026 deployment aggregates 10^6+ tasks per EM sweep). This
kernel streams (block_t, V) vote-index tiles through VMEM, gathers the
log-confusion rows with a one-hot MXU contraction (TPUs have no fast
vector gather; a (block_t, R) x (R, C) matmul against the resident
row table is the idiomatic replacement), accumulates the per-class
log-likelihood in registers, and emits BOTH the log-posterior and its
softmax in one pass. The (T, V, C) intermediate never touches HBM; traffic
is one read of the vote indices plus the (small) row table per tile.

Row-table layout (built by labelstream/aggregate.py): row ``w*C + l`` holds
``log P(vote=l | true=c, worker=w)`` for each true class c; row ``W*C`` is
an all-zero null row that padded/invalid votes point at, so masking costs
nothing inside the kernel. A uniform ``-log C`` prior initializes the
accumulator, which also makes zero-vote tasks come out exactly uniform.

Grid: (n_task_blocks,); the row table is resident in VMEM for every block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _ds_estep_kernel(idx_ref, rows_ref, logp_ref, post_ref, *, n_votes,
                     n_rows, c_total):
    idx = idx_ref[...]                                   # (block_t, V) int32
    block_t = idx.shape[0]
    cp = rows_ref.shape[1]
    # uniform prior over the real classes; padded class columns start at
    # NEG_INF so the fused softmax zeroes them without a separate mask
    col = jax.lax.broadcasted_iota(jnp.int32, (block_t, cp), 1)
    acc = jnp.where(col < c_total, -math.log(c_total), NEG_INF)
    rows = rows_ref[...].astype(jnp.float32)             # (R, Cp) resident
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (block_t, rows.shape[0]), 1)
    for v in range(n_votes):
        # one-hot MXU gather of each task's v-th vote row; padded votes hit
        # the all-zero null row so no mask is needed. fp32 contraction:
        # the gather must return the rows exactly, not rounded to bf16
        oh = (idx[:, v][:, None] == row_ids).astype(jnp.float32)
        acc = acc + jnp.dot(oh, rows, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    logp_ref[...] = acc
    m = acc.max(axis=1, keepdims=True)
    p = jnp.exp(acc - m)
    post_ref[...] = p / p.sum(axis=1, keepdims=True)


def ds_estep(rows, idx, *, block_t=128, interpret=False):
    """Fused DS log-posterior + softmax.

    rows: (R, C) float32 — log-confusion row table, R = n_workers*C + 1 with
          a trailing all-zero null row for padded votes.
    idx:  (T, V) int32 — per-vote row index (``w*C + label``; null row for
          invalid votes).
    Returns ``(logp, post)``, both (T, C) float32; ``logp`` includes the
    uniform ``-log C`` prior term.
    """
    T, V = idx.shape
    R, C = rows.shape
    if V == 0:
        logp = jnp.full((T, C), -math.log(C), jnp.float32)
        return logp, jnp.full((T, C), 1.0 / C, jnp.float32)
    block_t = min(block_t, max(8, T))
    pt = (-T) % block_t
    pr = (-R) % 128                  # contraction dim: lane-aligned
    pc = (-C) % 128                  # output lanes
    idx_p = jnp.pad(idx, ((0, pt), (0, 0)), constant_values=R - 1)
    # padded class columns are NEG_INF in every real row so the in-kernel
    # prior + softmax drive them to exactly zero mass; padded rows are never
    # selected (vote indices are < R)
    rows_p = jnp.pad(rows.astype(jnp.float32), ((0, 0), (0, pc)),
                     constant_values=NEG_INF)
    rows_p = rows_p.at[R - 1, C:].set(0.0)       # null row stays all-zero
    rows_p = jnp.pad(rows_p, ((0, pr), (0, 0)))
    Tp = T + pt

    logp, post = pl.pallas_call(
        functools.partial(_ds_estep_kernel, n_votes=V, n_rows=R, c_total=C),
        grid=(Tp // block_t,),
        in_specs=[
            pl.BlockSpec((block_t, V), lambda i: (i, 0)),
            pl.BlockSpec((R + pr, C + pc), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, C + pc), lambda i: (i, 0)),
            pl.BlockSpec((block_t, C + pc), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, C + pc), jnp.float32),
            jax.ShapeDtypeStruct((Tp, C + pc), jnp.float32),
        ],
        interpret=interpret,
    )(idx_p, rows_p)
    return logp[:T, :C], post[:T, :C]
