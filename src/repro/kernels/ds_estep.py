"""Pallas TPU fused Dawid-Skene E-step — labelstream's aggregation hot spot.

The E-step of Dawid-Skene EM scores every task's log-posterior over true
classes by summing, per vote, the voter's log-confusion row for the label it
gave, then softmax-normalizes. Done naively that is a (T, V, C) gather
materialized in HBM plus a separate softmax pass (T tasks, V votes/task,
C classes). This kernel gathers only the rows the votes name: the vote
indices are scalar-prefetched into SMEM, the row table stays in HBM
(``memory_space=pl.ANY``), and each tile of ``block_t`` tasks DMAs its
``block_t * V`` rows into a (V * block_t, 1, C) VMEM buffer, sums them
onto the uniform ``-log C`` prior, and emits BOTH the log-posterior and
its softmax in one pass. The (T, V, C) intermediate never touches HBM;
traffic is one read of the vote indices and of the rows they name. VMEM
holds one tile's rows whatever the table's size, so the kernel runs at
any number of rows and classes. In HBM the table is (rows, 1, C): a
single row is then a slice of the untiled leading axis, which is what a
one-row DMA may take.

Row-table layout: a row holds ``log P(vote=l | true=c, worker=w)`` over the
true classes c for one (worker w, label l) pair (labelstream/aggregate.py
keeps a row for each pair some vote names); the last row is an all-zero
null row that padded/invalid votes point at, so masking costs nothing
inside the kernel. A uniform ``-log C`` prior initializes the
accumulator, which also makes zero-vote tasks come out exactly uniform.

Grid: (n_tables, n_task_blocks). Under ``jax.vmap`` the batch becomes the
first grid axis (a ``custom_vmap`` rule): the tables stack in HBM and each
grid step reads its own table's rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _ds_estep_kernel(idx_ref, rows_hbm, logp_ref, post_ref, buf, sem, *,
                     n_votes, n_rows, n_tasks, c_total):
    b, i = pl.program_id(0), pl.program_id(1)
    block_t, cp = logp_ref.shape
    base = (b * n_tasks + i * block_t) * n_votes      # this tile's votes
    row0 = b * n_rows                                 # this table's rows

    def copy(t, v):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(row0 + idx_ref[base + t * n_votes + v], 1)],
            buf.at[pl.ds(v * block_t + t, 1)], sem.at[0])

    def start(t, c):
        for v in range(n_votes):
            copy(t, v).start()
        return c

    def wait(t, c):
        for v in range(n_votes):
            copy(t, v).wait()
        return c

    jax.lax.fori_loop(0, block_t, start, 0)
    jax.lax.fori_loop(0, block_t, wait, 0)
    # uniform prior over the real classes; padded class columns start at
    # NEG_INF (their rows are zero there) so the fused softmax zeroes them
    votes = buf[pl.ds(0, block_t)]
    for v in range(1, n_votes):
        votes = votes + buf[pl.ds(v * block_t, block_t)]
    col = jax.lax.broadcasted_iota(jnp.int32, (block_t, cp), 1)
    acc = jnp.where(col < c_total, -math.log(c_total), NEG_INF) \
        + votes.reshape(block_t, cp)
    logp_ref[...] = acc
    m = acc.max(axis=1, keepdims=True)
    p = jnp.exp(acc - m)
    post_ref[...] = p / p.sum(axis=1, keepdims=True)


def _ds_estep_batched(rows, idx, block_t, interpret):
    """rows (B, R, C), idx (B, T, V) -> (logp, post), each (B, T, C)."""
    B, T, V = idx.shape
    R, C = rows.shape[1:]
    if V == 0:
        logp = jnp.full((B, T, C), -math.log(C), jnp.float32)
        return logp, jnp.full((B, T, C), 1.0 / C, jnp.float32)
    block_t = min(block_t, -(-T // 8) * 8)
    pt = (-T) % block_t
    cp = C + (-C) % LANES                 # output lanes
    Tp = T + pt
    # padded tasks vote for the null row; the table's padded class columns
    # are zero (the in-kernel prior makes them NEG_INF)
    idx_p = jnp.pad(idx.astype(jnp.int32), ((0, 0), (0, pt), (0, 0)),
                    constant_values=R - 1)
    # one row per leading index (see the module docstring)
    rows_p = jnp.pad(rows.astype(jnp.float32),
                     ((0, 0), (0, 0), (0, cp - C))).reshape(B * R, 1, cp)
    out_spec = pl.BlockSpec((None, block_t, cp), lambda b, i, _: (b, i, 0))
    logp, post = pl.pallas_call(
        functools.partial(_ds_estep_kernel, n_votes=V, n_rows=R,
                          n_tasks=Tp, c_total=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Tp // block_t),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[out_spec, out_spec],
            scratch_shapes=[pltpu.VMEM((V * block_t, 1, cp), jnp.float32),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=[jax.ShapeDtypeStruct((B, Tp, cp), jnp.float32)] * 2,
        interpret=interpret, name="ds_estep",
    )(idx_p.reshape(-1), rows_p)
    return logp[:, :T, :C], post[:, :T, :C]


@functools.lru_cache(maxsize=None)
def _ds_estep_fn(block_t: int, interpret: bool):
    """The kernel for one table, with a ``custom_vmap`` rule that runs a
    batch of tables as one kernel (the grid's first axis)."""

    @jax.custom_batching.custom_vmap
    def one(rows, idx):
        logp, post = _ds_estep_batched(rows[None], idx[None], block_t,
                                       interpret)
        return logp[0], post[0]

    @one.def_vmap
    def _(axis_size, in_batched, rows, idx):
        rows, idx = (x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
                     for x, b in zip((rows, idx), in_batched))
        return _ds_estep_batched(rows, idx, block_t, interpret), (True, True)

    return one


def ds_estep(rows, idx, *, block_t=128, interpret=False):
    """Fused DS log-posterior + softmax.

    rows: (R, C) float32 — log-confusion row table, one row per (worker,
          label) pair and a trailing all-zero null row for padded votes.
    idx:  (T, V) int32 — per-vote row index (the row of the vote's worker
          and label; the null row ``R - 1`` for invalid votes).
    Returns ``(logp, post)``, both (T, C) float32; ``logp`` includes the
    uniform ``-log C`` prior term. ``jax.vmap`` over both runs one kernel
    over every table.
    """
    return _ds_estep_fn(int(block_t), bool(interpret))(rows, idx)
