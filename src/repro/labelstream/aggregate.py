"""Batched full-confusion Dawid-Skene EM in pure JAX.

The scalar dict-based one-coin EM in ``core/quality.py`` is a dead end for
scale: Python loops over tasks and votes, one replication at a time. This
module is the vectorized replacement and the engine behind
``quality.em_worker_accuracy``:

  * votes live in dense padded arrays — ``labels``/``workers`` (T, V) int32
    with a validity ``mask`` — produced by :func:`pack_votes`;
  * the E-step is one fused gather+softmax over a log-confusion row table
    (a row per (worker w, label l) pair some vote names, holding
    ``log P(vote=l | true=c)``), either as pure jnp or through the Pallas
    kernel ``kernels/ds_estep.py`` (interpret mode on CPU, Mosaic on TPU);
  * the M-step is a padded scatter-add of posteriors into those
    (worker, label) rows and into per-worker totals — the same segment-sum
    idiom as simfast's vote accumulation. The rows stay in that layout
    across iterations, and at most one per vote exists, so an iteration
    moves O(T V C) bytes however many workers and classes there are; the
    whole (W, C, C) confusion is built once, after the last iteration;
  * EM iterations run under ``lax.scan``; independent replications vmap
    through :func:`dawid_skene_batch`.

Two observation models:
  * ``one_coin=True``  — symmetric accuracy per worker, numerically
    identical to ``quality.em_worker_accuracy_ref`` (same 0.8 init, same
    +1/+2 Beta smoothing, same accuracy clipping) so the parity tests can
    assert exact agreement;
  * ``one_coin=False`` — full C x C confusion matrix per worker with
    Laplace-smoothed rows, which additionally captures class-dependent
    error (a worker who always answers 0 stops dragging class-0 tasks);
    a worker's accuracy is the smoothed share of its posterior-weighted
    votes on the diagonal, so it stays meaningful when C is large and a
    worker has seen few of the classes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

ACC_CLIP = 1e-3          # matches quality.em_worker_accuracy_ref
CONF_CLIP = 1e-6
INIT_ACC = 0.8


class VotePack(NamedTuple):
    """Dense vote table + the worker-id mapping used to build it."""
    labels: np.ndarray       # (T, V) int32 vote labels
    workers: np.ndarray      # (T, V) int32 dense worker indices
    mask: np.ndarray         # (T, V) bool validity
    worker_ids: list         # dense index -> original worker id


def _bucket(n: int, step: int) -> int:
    return max(step, -(-n // step) * step)


def pack_votes(task_votes, *, pad_tasks_to: Optional[int] = None,
               pad_votes_to: Optional[int] = None,
               pad_workers_to: Optional[int] = None
               ) -> "tuple[VotePack, int]":
    """Pack ``[[(label, worker_id), ...], ...]`` into dense padded arrays.

    Returns ``(pack, n_workers)`` — the dense vote table and the (bucket-
    padded) worker-axis size to hand to :func:`dawid_skene`. Shapes are
    bucket-padded (tasks to 32, votes to 4, workers to 8) so repeated
    callers with drifting sizes — e.g. the Maintainer's rolling vote
    window — hit a handful of jit cache entries instead of one per call.
    Tasks with empty vote lists are legal and come out fully masked.
    """
    ids = sorted({w for votes in task_votes for _, w in votes})
    wid_to_dense = {w: i for i, w in enumerate(ids)}
    T = len(task_votes)
    V = max((len(v) for v in task_votes), default=0)
    Tp = pad_tasks_to or _bucket(T, 32)
    Vp = pad_votes_to or _bucket(V, 4)
    labels = np.zeros((Tp, Vp), np.int32)
    workers = np.zeros((Tp, Vp), np.int32)
    mask = np.zeros((Tp, Vp), bool)
    for i, votes in enumerate(task_votes):
        for j, (label, wid) in enumerate(votes):
            labels[i, j] = label
            workers[i, j] = wid_to_dense[wid]
            mask[i, j] = True
    n_workers = pad_workers_to or _bucket(max(len(ids), 1), 8)
    if n_workers < len(ids):
        raise ValueError("pad_workers_to smaller than distinct workers")
    pack = VotePack(labels, workers, mask, ids)
    return pack, n_workers


def _row_table(log_conf_rows, n_classes):
    """(K, C_true) log-confusion rows -> the E-step's row table: a
    trailing all-zero null row for masked votes appended (index K)."""
    return jnp.concatenate([log_conf_rows,
                            jnp.zeros((1, n_classes), log_conf_rows.dtype)])


def estep_mode(use_kernel: Optional[bool] = None) -> "tuple[bool, bool]":
    """``(use_kernel, interpret)`` for the E-step on the current backend.

    ``use_kernel=None`` selects the fused Pallas E-step on TPU and the
    pure-jnp path elsewhere; an explicit ``use_kernel=True`` runs the
    kernel everywhere (interpret mode off-TPU)."""
    on_tpu = jax.default_backend() == "tpu"
    return (on_tpu if use_kernel is None else bool(use_kernel)), not on_tpu


def _estep(log_conf_rows, idx, n_classes, use_kernel, interpret):
    rows = _row_table(log_conf_rows, n_classes)
    if use_kernel:
        from repro.kernels.ds_estep import ds_estep
        logp, post = ds_estep(rows, idx, interpret=interpret)
        return logp, post
    from repro.kernels import ref
    logp, post = ref.ds_estep_ref(rows, idx)
    return logp, post


def _ds_em(labels, workers, mask, n_workers, n_classes, iters, one_coin,
           use_kernel, interpret):
    T, V = labels.shape
    W, C = n_workers, n_classes
    R, N = W * C, T * V
    # vote j's confusion row is (worker, label) pair w*C + l; only the
    # pairs some vote names are ever read, so the rows are kept for those
    # alone: K = N slots, slot k holding pair ``pair[k]`` (the masked
    # votes' pair R and the unused slots are never read)
    valid = mask.reshape(-1)
    key = jnp.where(mask, workers * C + labels, R).reshape(-1)
    pair, slot = jnp.unique(key, size=N, fill_value=R, return_inverse=True)
    slot = slot.reshape(-1)
    pair_w, pair_l = pair // C, pair % C            # pair R -> worker W
    # the E-step's row index: the vote's slot, masked votes the null row
    idx = jnp.where(valid, slot, N).reshape(T, V).astype(jnp.int32)
    w_v = jnp.where(valid, workers.reshape(-1), W)
    l_v = labels.reshape(-1)
    t_v = jnp.repeat(jnp.arange(T), V)
    votes_per_worker = jnp.zeros((W + 1,)).at[w_v].add(1.0)[:W]
    maskf = mask.astype(jnp.float32)
    cls = jnp.arange(C)

    def conf_from_acc(acc, w, l):
        """Rows (w, l) of the one-coin confusion at accuracies ``acc``."""
        a = jnp.clip(acc, ACC_CLIP, 1.0 - ACC_CLIP)[w]
        off = (1.0 - a) / max(C - 1, 1)
        return jnp.where(cls[None, :] == l[:, None], a[:, None],
                         off[:, None])

    def mstep(post):
        # each vote's posterior into its pair's row and its worker's
        # totals: padded segment-sums over the N votes, no (W, C, C) table
        post_v = post[t_v] * valid[:, None]
        diag = jnp.zeros((W + 1,)).at[w_v].add(
            jnp.take_along_axis(post_v, l_v[:, None], 1)[:, 0])[:W]
        if one_coin:
            # Beta(1,1)-smoothed symmetric accuracy — identical to the
            # scalar reference's num/den update
            acc = (1.0 + diag) / (2.0 + jnp.maximum(votes_per_worker, 0.0))
            return conf_from_acc(acc, jnp.minimum(pair_w, W - 1), pair_l), \
                acc
        counts = jnp.zeros((N, C)).at[slot].add(post_v)      # (pair, true)
        row_tot = jnp.zeros((W + 1, C)).at[w_v].add(post_v)  # (W+1, true)
        conf = (counts + 1.0 / C) / (row_tot[pair_w] + 1.0)   # Laplace rows
        # the share of the worker's posterior-weighted votes the EM
        # explains as correct, with the rows' smoothing (one pseudo-vote
        # spread over the C labels); the mean of the confusion's diagonal
        # would weigh the classes the worker never saw as uniform rows
        acc = (diag + 1.0 / C) / (row_tot[:W].sum(-1) + 1.0)
        return conf, acc

    def dense_confusion(post, acc):
        """The last M-step's whole (W, true, vote) confusion, pairs no vote
        names included (computed once, for callers that read it)."""
        eye = jnp.eye(C, dtype=jnp.float32)
        if one_coin:
            a = jnp.clip(acc, ACC_CLIP, 1.0 - ACC_CLIP)[:, None, None]
            return a * eye + (1.0 - a) / max(C - 1, 1) * (1.0 - eye)
        counts = (jnp.zeros((R + 1, C)).at[key].add(post[t_v]
                                                    * valid[:, None]))
        counts = counts[:R].reshape(W, C, C)              # (W, vote, true)
        row_tot = counts.sum(1)
        return ((counts + 1.0 / C) / (row_tot[:, None, :] + 1.0)) \
            .transpose(0, 2, 1)

    conf0 = conf_from_acc(jnp.full((W,), INIT_ACC),
                          jnp.minimum(pair_w, W - 1), pair_l)

    def body(carry, _):
        conf, _acc, _logp, _post = carry
        logp, post = _estep(jnp.log(jnp.clip(conf, CONF_CLIP, 1.0)), idx,
                            C, use_kernel, interpret)
        conf, acc = mstep(post)
        # the E-step output rides in the carry (not the stacked ys), so
        # only the last iteration's O(T*C) posterior is materialized
        return (conf, acc, logp, post), None

    (conf, acc, logp, post), _ = jax.lax.scan(
        body, (conf0, jnp.full((W,), INIT_ACC), jnp.zeros((T, C)),
               jnp.full((T, C), 1.0 / C)), None, length=iters)
    # scalar reference order: labels come from the E-step of the LAST
    # iteration, accuracies from the M-step that follows it
    return dict(log_posterior=logp, posterior=post,
                confusion=dense_confusion(post, acc), accuracy=acc,
                n_votes=maskf.sum(-1), votes_per_worker=votes_per_worker)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _ds_jit(labels, workers, mask, n_workers, n_classes, iters, one_coin,
            use_kernel, interpret):
    return _ds_em(labels, workers, mask, n_workers, n_classes, iters,
                  one_coin, use_kernel, interpret)


def dawid_skene(labels, workers, mask, *, n_workers: int, n_classes: int,
                iters: int = 20, one_coin: bool = False,
                use_kernel: Optional[bool] = None):
    """Vectorized Dawid-Skene EM over a dense padded vote table.

    labels/workers: (T, V) int32; mask: (T, V) bool. Returns a dict with
    ``posterior`` (T, C), ``log_posterior`` (T, C), ``confusion`` (W, C, C),
    ``accuracy`` (W,), ``n_votes`` (T,) and ``votes_per_worker`` (W,).

    ``use_kernel=None`` auto-selects: the fused Pallas E-step on TPU, the
    pure-jnp path elsewhere (the kernel still runs everywhere via
    ``use_kernel=True`` — interpret mode off-TPU).
    """
    use_kernel, interpret = estep_mode(use_kernel)
    return _ds_jit(jnp.asarray(labels, jnp.int32),
                   jnp.asarray(workers, jnp.int32),
                   jnp.asarray(mask, bool),
                   int(n_workers), int(n_classes), int(iters),
                   bool(one_coin), use_kernel, interpret)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _ds_batch_jit(labels, workers, mask, n_workers, n_classes, iters,
                  one_coin, use_kernel, interpret):
    return jax.vmap(
        lambda l, w, m: _ds_em(l, w, m, n_workers, n_classes, iters,
                               one_coin, use_kernel, interpret)
    )(labels, workers, mask)


def dawid_skene_batch(labels, workers, mask, *, n_workers: int,
                      n_classes: int, iters: int = 20, one_coin: bool = False,
                      use_kernel: Optional[bool] = None):
    """vmap of :func:`dawid_skene` over a leading replication axis.

    labels/workers/mask: (n_reps, T, V). Each replication runs its own EM
    (scan over iterations) in lock-step. Jitted through a module-level
    cache, so repeated same-shaped calls do not retrace.
    """
    use_kernel, interpret = estep_mode(use_kernel)
    return _ds_batch_jit(jnp.asarray(labels, jnp.int32),
                         jnp.asarray(workers, jnp.int32),
                         jnp.asarray(mask, bool),
                         int(n_workers), int(n_classes), int(iters),
                         bool(one_coin), use_kernel, interpret)


def aggregate_votes(task_votes, n_classes: int, *, iters: int = 20,
                    one_coin: bool = True,
                    use_kernel: Optional[bool] = None):
    """List-of-votes front door: pack, run EM, unpack to python types.

    Returns ``(labels, acc_by_worker, out)`` where ``labels`` is a list of
    posterior-argmax labels (len == len(task_votes)), ``acc_by_worker`` maps
    original worker ids to estimated accuracy, and ``out`` is the raw
    :func:`dawid_skene` result (padded shapes).
    """
    T = len(task_votes)
    (pack, n_workers) = pack_votes(task_votes)
    if not pack.worker_ids or n_classes < 2:
        return [0] * T, {w: INIT_ACC for w in pack.worker_ids}, None
    out = dawid_skene(pack.labels, pack.workers, pack.mask,
                      n_workers=n_workers, n_classes=n_classes, iters=iters,
                      one_coin=one_coin, use_kernel=use_kernel)
    post = np.asarray(out["posterior"])[:T]
    acc = np.asarray(out["accuracy"])
    labels = [int(c) for c in post.argmax(-1)]
    acc_by_worker = {w: float(acc[i]) for i, w in enumerate(pack.worker_ids)}
    return labels, acc_by_worker, out
