"""Plain reference of one serve tick's answer half with the offline
Dawid-Skene refresh, in numpy.

``reference/crowd_tick.py`` with one phase inserted where the router runs
it: after the votes (step 2 and 3 there) and before fusion and
finalization (steps 4 and 5), on the ticks whose step ``s`` has
``s % refresh_every == refresh_every - 1``. The refresh re-explains every
stored vote of the window's active tasks by full-confusion Dawid-Skene EM
(Dawid & Skene 1979, Applied Statistics 28(1)) and then

- each active task with a vote takes the EM's log-posterior in place of
  its online one;
- each worker's running estimates become the EM's: ``est_n`` its votes in
  the window, ``est_correct`` that count times its EM accuracy.

The EM follows Dawid & Skene with the repository's documented choices,
each a departure from the published description:

- it starts from a one-coin confusion at accuracy 0.8 (right with 0.8,
  each wrong label with 0.2 / (C - 1)), not from a majority vote;
- the class prior is uniform and fixed, not estimated;
- a confusion row is Laplace-smoothed, ``(counts + 1/C) / (row_tot + 1)``,
  not the maximum-likelihood ratio, and log-confusions are clipped at
  1e-6;
- it runs a fixed ``refresh_iters`` iterations with no convergence test;
  the labels come from the last E-step, the accuracies from the M-step
  after it;
- a worker's accuracy (no part of the published model) is the smoothed
  share of its posterior-weighted votes on the diagonal,
  ``(sum_c counts[c, c] + 1/C) / (votes + 1)``;
- workers are the shard's pool slots, and the votes of workers who left
  (remapped to slot ``pool_size``) count as one more worker's.

The M-step keeps only the confusion rows of labels some worker gave (the
E-step never reads another), so a shard's EM fits in host memory at any
number of classes. Only the deployment without the learner and with
Gaussian task identity is reproduced. It imports nothing of the program.

``dtype`` sets the precision of the posterior arithmetic, the EM
included: float32 as the deployment states, or ``bfloat16`` for the
control.
"""
from __future__ import annotations

import numpy as np

from reference.crowd_tick import _cast, confidence, uniform_block

INIT_ACC = 0.8
CONF_CLIP = 1e-6


def refresh_step(p: dict, step: int) -> bool:
    """Whether the tick at ``step`` runs the refresh."""
    k = p.get("refresh_every", 0)
    return k > 0 and step % k == k - 1


def dawid_skene(labels, workers, mask, n_workers: int, n_classes: int,
                iters: int, dtype="float32"):
    """Full-confusion EM over one shard's vote log: ``labels``,
    ``workers`` (T, V) ints and ``mask`` (T, V) bool. Returns the last
    E-step's log-posterior (T, C) with the uniform ``-log C`` prior, each
    worker's accuracy (W,) from the M-step after it, and its vote count
    (W,)."""
    f32 = np.float32
    T, V = labels.shape
    C = n_classes
    w_v, l_v = workers[mask], labels[mask]
    t_v = np.nonzero(mask)[0]
    # the (worker, label) pairs the votes name, and each vote's pair
    pairs, pair_of = np.unique(np.stack([w_v, l_v], 1), axis=0,
                               return_inverse=True)
    pair_of = pair_of.reshape(-1)
    pw, pl = pairs[:, 0], pairs[:, 1]
    vpw = np.bincount(w_v, minlength=n_workers).astype(f32)
    # first E-step: the one-coin confusion at INIT_ACC
    wrong = f32((1.0 - INIT_ACC) / max(C - 1, 1))
    rows = np.full((len(pairs), C), wrong, f32)
    rows[np.arange(len(pairs)), pl] = f32(INIT_ACC)
    acc = np.full((n_workers,), f32(INIT_ACC))
    logp = np.full((T, C), f32(-np.log(C)))
    for _ in range(iters):
        lrow = _cast(np.log(np.clip(rows, f32(CONF_CLIP), f32(1.0))), dtype)
        logp = np.full((T, C), f32(-np.log(C)))
        np.add.at(logp, t_v, lrow[pair_of])
        logp = _cast(logp, dtype)
        z = _cast(logp - logp.max(-1, keepdims=True), dtype)
        e = _cast(np.exp(z), dtype)
        post = _cast(e / _cast(e.sum(-1, keepdims=True), dtype), dtype)
        # M-step: each vote's posterior into its (worker, label) row
        counts = np.zeros((len(pairs), C), f32)
        np.add.at(counts, pair_of, post[t_v])
        counts = _cast(counts, dtype)
        row_tot = np.zeros((n_workers, C), f32)
        np.add.at(row_tot, pw, counts)
        row_tot = _cast(row_tot, dtype)
        rows = _cast((counts + f32(1.0 / C)) / (row_tot[pw] + f32(1.0)),
                     dtype)
        diag = np.zeros((n_workers,), f32)
        np.add.at(diag, pw, counts[np.arange(len(pairs)), pl])
        acc = _cast((diag + f32(1.0 / C)) / (row_tot.sum(-1) + f32(1.0)),
                    dtype)
    return logp.astype(f32), acc.astype(f32), vpw


def shard_tick(p: dict, pre: dict, s: int, n_arr: int, uid_base: int,
               dtype="float32"):
    """Shard ``s``'s part of one tick, as ``crowd_tick.shard_tick`` (whose
    docstring gives the parameters and returns) with the refresh on the
    ticks that run it."""
    if p["feature_kind"] == "lm" or p.get("learner"):
        raise ValueError("the refresh reference covers the deployment "
                         "without the learner and text features only")
    f32 = np.float32
    P, Ws, Q, M = p["pool_size"], p["window"], p["backlog"], p["max_arrivals"]
    C, cap = p["n_classes"], p["votes_cap"]
    t, step = f32(pre["t"]), int(pre["step"])
    seed = int(pre["seeds"][s])
    ws = {k: np.array(v[s]) for k, v in pre["ws"].items()}
    win = {k: np.array(v[s]) for k, v in pre["win"].items()}
    bl = {k: np.array(v[s]) for k, v in pre["bl"].items()}
    up = uniform_block(seed, step, 8 * P).reshape(8, P)

    # ---- backlog push + FIFO admission -------------------------------
    free = ~win["active"]
    frank = np.cumsum(free) - 1
    head, count = int(bl["head"]), int(bl["count"])
    n_push = min(int(n_arr), Q - count)
    slot = np.arange(M)
    ok = slot < n_push
    pos = (head + count + slot) % Q
    bl["times"][pos[ok]] = t
    bl["uid"][pos[ok]] = uid_base + slot[ok]
    count += n_push
    n_adm = min(count, int(free.sum()))
    admit = free & (frank < n_adm)
    src = (head + frank[admit]) % Q
    bl["head"] = np.int32((head + n_adm) % Q)
    bl["count"] = np.int32(count - n_adm)
    win["active"] = win["active"] | admit
    win["arrival_t"][admit] = bl["times"][src]
    win["uid"][admit] = bl["uid"][src]
    uw = uniform_block(seed ^ 0x33CC33CC, step, 2 * Ws).reshape(2, Ws)
    diff = np.where(uw[0] < f32(p["p_hard"]), f32(p["hard_scale"]),
                    f32(1.0)).astype(np.float32)
    tl = np.clip(np.floor(uw[1] * C).astype(np.int32), 0, C - 1)
    win["difficulty"][admit] = diff[admit]
    win["true_label"][admit] = tl[admit]
    win["n_votes"][admit] = 0
    win["logpost"][admit] = 0.0

    # ---- completions -> votes -> online posterior --------------------
    assigned = ws["assigned"]
    active_w = assigned >= 0
    comp = active_w & (ws["busy_until"] <= t)
    a_idx = np.maximum(assigned, 0)
    d_w = win["difficulty"][a_idx]
    inv_c = f32(1.0 / C)
    p_corr = np.clip(inv_c + (ws["acc"] - inv_c) * d_w, inv_c, f32(0.995))
    tl_w = win["true_label"][a_idx]
    correct = up[0] < p_corr
    wrong = np.floor(up[1] * max(C - 1, 1)).astype(np.int32)
    label = np.where(correct, tl_w, np.where(wrong >= tl_w, wrong + 1, wrong))
    a_e = np.clip((f32(p["est_prior_acc"] * p["est_prior_n"])
                   + ws["est_correct"]) / (f32(p["est_prior_n"])
                                           + ws["est_n"]),
                  f32(0.52), f32(0.995)).astype(np.float32)
    delta = _cast(np.log(_cast(a_e * f32(max(C - 1, 1)) / (f32(1.0) - a_e),
                               dtype)), dtype)
    n_before = win["n_votes"].copy()
    seen = {}
    for w in np.nonzero(comp)[0]:
        tid = int(assigned[w])
        vpos = int(n_before[tid]) + seen.get(tid, 0)
        seen[tid] = seen.get(tid, 0) + 1
        if vpos >= cap:
            continue
        win["vote_wid"][tid, vpos] = w
        win["vote_lab"][tid, vpos] = label[w]
        win["logpost"][tid, label[w]] = _cast(
            win["logpost"][tid, label[w]] + delta[w], dtype)
        win["n_votes"][tid] += 1

    # ---- the offline refresh -----------------------------------------
    refreshed = refresh_step(p, step)
    refresh_votes = 0
    if refreshed:
        nv, act = win["n_votes"], win["active"]
        vmask = (np.arange(cap)[None, :] < nv[:, None]) & act[:, None]
        refresh_votes = int(vmask.sum())
        lp_em, acc, vpw = dawid_skene(
            win["vote_lab"][:Ws], win["vote_wid"][:Ws], vmask, P + 1, C,
            p["refresh_iters"], dtype)
        has = act & (nv > 0)
        win["logpost"][has] = lp_em[has]
        ws["est_correct"] = (acc[:P] * vpw[:P]).astype(np.float32)
        ws["est_n"] = vpw[:P]

    # ---- finalization ------------------------------------------------
    lp = _cast(win["logpost"], dtype)
    nv = win["n_votes"]
    conf = confidence(lp, dtype)
    early = p["adaptive"] & (conf >= f32(p["conf_threshold"])) \
        & (nv >= p["min_votes"])
    fin = (nv > 0) & (early | (nv >= cap)) & win["active"]
    srt = np.sort(lp, -1)
    margins = dict(conf=np.abs(conf - f32(p["conf_threshold"])),
                   label=srt[:, -1] - srt[:, -2])
    result = lp.argmax(-1).astype(np.int32)
    out = dict(fin=fin, known=np.zeros((Ws,), bool), uid=win["uid"].copy(),
               label=result,
               votes=nv.copy(), conf=conf,
               tis=np.where(fin, t - win["arrival_t"], f32(0.0)),
               dropped=np.int32(int(n_arr) - n_push))
    post = dict(win=win, bl=bl, ws=ws, admit=admit, comp=comp,
                active_w=active_w, refreshed=refreshed,
                refresh_votes=refresh_votes)
    return out, post, margins

