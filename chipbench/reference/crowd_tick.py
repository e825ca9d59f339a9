"""Plain reference of one serve tick's answer half, in numpy.

Given the router state before a tick (copied to the host), the tick's
injection (per-shard counts, uid bases, and for text submissions their
embeddings and known labels) and the deployment's parameters from the
configuration file, this computes what the tick must answer and how the
answer half of the state must move:

1. backlog push and FIFO admission into free window slots, with each
   admitted task's request uid, arrival time, difficulty and true label;
2. completed assignments turn into votes: a worker of accuracy ``acc`` on a
   task of difficulty ``d`` is right with probability
   ``clip(1/C + (acc - 1/C) d, 1/C, 0.995)``, drawn from the tick's hashed
   uniforms; votes past the cap are dropped;
3. the online one-coin Dawid-Skene E-step: each vote adds its worker's
   estimated log-odds ``log(a (C-1) / (1 - a))`` to the voted class, with
   ``a`` the Beta-smoothed, clipped running accuracy estimate;
4. with the learner on, the model's log-posterior is fused in (product of
   experts) and model-known tasks may finalize;
5. adaptive redundancy: a task finalizes when its confidence reaches the
   threshold with enough votes, or at the vote cap; its answer is the
   posterior's argmax, its confidence the largest posterior mass, and its
   time in system ``t - arrival``;
6. the incremental M-step credits each finalized task's voters by
   agreement with the answer; workers whose session ended leave and their
   estimates reset;
7. with the learner on, the finalized tasks with a crowd vote join the
   learner's replay ring as (features, crowd-only argmax) pairs, and every
   few ticks the learner takes its Adam steps on the ring.

It imports nothing of the program. The worker half of the tick
(matching, latency draws, recruitment) is not reproduced: it decides which
votes later ticks see, not what this tick answers.

``dtype`` sets the precision of the posterior and learner arithmetic
(steps 3 to 5 and 7): float32 as the deployment states, or ``bfloat16``
for the control.
"""
from __future__ import annotations

import numpy as np

_U32 = np.uint32


def lowbias32(x):
    x = x.astype(_U32)
    x = x ^ (x >> _U32(16))
    x = (x * _U32(0x7FEB352D)).astype(_U32)
    x = x ^ (x >> _U32(15))
    x = (x * _U32(0x846CA68B)).astype(_U32)
    return x ^ (x >> _U32(16))


def uniform_block(seed, step, n: int):
    """(n,) float32 uniforms in [0, 1) from the (seed, step) counters."""
    with np.errstate(over="ignore"):
        base = lowbias32(np.asarray(
            _U32(seed) ^ (_U32(np.uint32(step)) * _U32(0x9E3779B9)), _U32))
        h = lowbias32(base + np.arange(n, dtype=_U32) * _U32(0x85EBCA6B))
    return (h >> _U32(8)).astype(np.float32) * np.float32(1.0 / (1 << 24))


def _cast(x, dtype):
    if dtype == "bfloat16":
        # round to nearest even on the top 16 bits of the float32 pattern
        b = np.asarray(x, np.float32).view(_U32)
        b = (b + _U32(0x7FFF) + ((b >> _U32(16)) & _U32(1))) & _U32(0xFFFF0000)
        return b.view(np.float32)
    return np.asarray(x, np.float32)


def _log_softmax(x, dtype):
    m = x.max(-1, keepdims=True)
    z = _cast(x - m, dtype)
    return _cast(z - _cast(np.log(_cast(np.exp(z), dtype).sum(-1,
                                                               keepdims=True)),
                           dtype), dtype)


def confidence(lp, dtype="float32"):
    """Largest posterior mass of unnormalized log-posteriors."""
    z = _cast(lp - lp.max(-1, keepdims=True), dtype)
    e = _cast(np.exp(z), dtype)
    return _cast(e.max(-1) / _cast(e.sum(-1), dtype), dtype)


def shard_tick(p: dict, pre: dict, s: int, n_arr: int, uid_base: int,
               feat_in=None, labels_in=None, learner=None,
               dtype="float32"):
    """Shard ``s``'s part of one tick. ``p`` holds the deployment's
    parameters, ``pre`` the whole pre-tick state (host arrays). Returns
    ``(out, post, margins)``: the tick's per-slot answers, the answer half
    of the post-tick state, and for each slot how far its confidence lies
    from the finalize threshold and its best class from the runner-up."""
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
    lm = p["feature_kind"] == "lm"
    if lm:
        if feat_in is None:        # no text this tick: nothing injected
            feat_in = np.full((M, win["feat"].shape[-1]), np.nan, np.float32)
            labels_in = np.full((M,), -1, np.int32)
        ua = uniform_block(seed ^ 0x0BAD5EED, step, 3 * M).reshape(3, M)
        diff_a = np.where(ua[0] < f32(p["p_hard"]), f32(p["hard_scale"]),
                          f32(1.0)).astype(np.float32)
        tl_a = np.clip(np.floor(ua[1] * C).astype(np.int32), 0, C - 1)
        tl_a = np.where(labels_in >= 0, labels_in, tl_a)
        if not np.isfinite(feat_in[ok, 0]).all():
            raise ValueError("the reference needs every injected task to "
                             "carry its text embedding")
        bl["tlab"][pos[ok]] = tl_a[ok]
        bl["diff"][pos[ok]] = diff_a[ok]
        bl["feat"][pos[ok]] = feat_in[ok]
    count += n_push
    n_adm = min(count, int(free.sum()))
    admit = free & (frank < n_adm)
    src = (head + frank[admit]) % Q
    bl["head"] = np.int32((head + n_adm) % Q)
    bl["count"] = np.int32(count - n_adm)
    win["active"] = win["active"] | admit
    win["arrival_t"][admit] = bl["times"][src]
    win["uid"][admit] = bl["uid"][src]
    if lm:
        win["difficulty"][admit] = bl["diff"][src]
        win["true_label"][admit] = bl["tlab"][src]
        win["feat"][admit] = bl["feat"][src]
    else:
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

    # ---- learner fusion, finalization --------------------------------
    lp = _cast(win["logpost"], dtype)
    nv = win["n_votes"]
    if learner is not None:
        # the logits' matrix product rounds its inputs as the deployment
        # states (bfloat16: one pass of the chip's matrix unit)
        mi = p.get("learner_matmul_inputs", "float32")
        logits = _cast(_cast(win["feat"], mi) @ _cast(learner["W"], mi)
                       + learner["b"], dtype)
        fused = _cast(lp + f32(learner["fuse_w"])
                      * _log_softmax(logits, dtype), dtype)
        kconf = confidence(fused, dtype)
        known_fin = (kconf >= f32(p["known_threshold"])) \
            & (nv >= p["min_votes_known"])
        kmargin = np.abs(kconf - f32(p["known_threshold"]))
    else:
        fused = lp
        known_fin = np.zeros((Ws,), bool)
        kmargin = np.full((Ws,), np.inf, np.float32)
    conf = confidence(fused, dtype)
    early = p["adaptive"] & (conf >= f32(p["conf_threshold"])) \
        & (nv >= p["min_votes"])
    fin = ((nv > 0) & (early | (nv >= cap))) | known_fin
    fin = fin & win["active"]
    srt = np.sort(fused, -1)
    crowd = np.sort(lp, -1)
    margins = dict(
        conf=np.minimum(np.abs(conf - f32(p["conf_threshold"])), kmargin),
        label=srt[:, -1] - srt[:, -2] if C > 1 else np.full((Ws,), np.inf),
        crowd=crowd[:, -1] - crowd[:, -2] if C > 1
        else np.full((Ws,), np.inf))
    result = fused.argmax(-1).astype(np.int32)
    out = dict(fin=fin, known=known_fin & win["active"],
               uid=win["uid"].copy(), label=result, votes=nv.copy(), conf=conf,
               tis=np.where(fin, t - win["arrival_t"], f32(0.0)),
               dropped=np.int32(int(n_arr) - n_push))
    post = dict(win=win, bl=bl, ws=ws, admit=admit, comp=comp,
                active_w=active_w)
    return out, post, margins


def finish_shard(p: dict, post: dict, fin, result, pre_t):
    """Steps 6 of the module docstring for one shard, given the tick's
    final ``fin`` and ``result`` (the reference's own, or the program's
    where the reference's decision lay within rounding of a boundary)."""
    P = p["pool_size"]
    win, ws = post["win"], post["ws"]
    t = np.float32(pre_t)
    nv = win["n_votes"]
    for slot in np.nonzero(fin)[0]:
        for j in range(int(nv[slot])):
            w = int(win["vote_wid"][slot, j])
            if w < P:
                ws["est_n"][w] += 1.0
                ws["est_correct"][w] += float(
                    win["vote_lab"][slot, j] == result[slot])
    win["active"] = win["active"] & ~fin
    assigned = ws["assigned"]
    a_idx = np.maximum(assigned, 0)
    comp, active_w = post["comp"], post["active_w"]
    lose = active_w & ~comp & fin[a_idx]
    freed = comp | lose
    assigned = np.where(freed, -1, assigned)
    blocked = np.where(comp, ws["busy_until"],
                       np.where(lose, t + np.float32(p["switch_delay_s"]),
                                ws["blocked_until"]))
    leave = (assigned < 0) & (blocked <= t) & (ws["session_end"] <= t)
    ws["est_correct"][leave] = 0.0
    ws["est_n"][leave] = 0.0
    leave_pad = np.concatenate([leave, [False]])
    win["vote_wid"] = np.where(leave_pad[win["vote_wid"]], P,
                               win["vote_wid"]).astype(np.int32)
    return post


def learner_update(p: dict, lrn: dict, step: int, feat, label,
                   dtype="float32") -> dict:
    """Step 7 of the module docstring for the whole tick. ``lrn`` holds the
    learner before the tick (``W``, ``b``, Adam's ``mW``, ``mb``, ``vW``,
    ``vb`` and step ``t``; the ring ``buf_X``, ``buf_y`` of
    ``learner_buffer`` + 1 rows and its count ``buf_n``), ``feat`` and
    ``label`` the tick's training pairs in shard-major slot order. The
    pairs are written after the ring's newest; when ``step`` is a multiple
    of ``learner_fit_every`` the learner takes ``learner_fit_steps``
    bias-corrected Adam steps on the mean cross-entropy of the ring's
    filled rows plus ``learner_l2 |W|^2``, moments carried over. Matrix
    products round their inputs as ``learner_fit_matmul_inputs`` says."""
    f32 = np.float32
    B = p["learner_buffer"]
    mi = p.get("learner_fit_matmul_inputs", "float32")
    new = {k: np.array(v) for k, v in lrn.items()}
    n0, k = int(lrn["buf_n"]), len(label)
    pos = (n0 + np.arange(k)) % B
    new["buf_X"][pos] = feat
    new["buf_y"][pos] = label
    n = n0 + k
    new["buf_n"] = np.int32(n)
    if step % p["learner_fit_every"] or n <= 0:
        return new
    X, y = new["buf_X"][:B], new["buf_y"][:B]
    sw = (np.arange(B) < n).astype(f32)
    den = f32(max(float(sw.sum()), 1e-9))
    onehot = np.eye(new["W"].shape[1], dtype=f32)[y]
    lr, l2 = f32(p["learner_lr"]), f32(p["learner_l2"])
    for _ in range(p["learner_fit_steps"]):
        W, b = new["W"], new["b"]
        logits = _cast(_cast(X, mi) @ _cast(W, mi) + b, dtype)
        z = _cast(logits - logits.max(-1, keepdims=True), dtype)
        e = _cast(np.exp(z), dtype)
        d = _cast((e / e.sum(-1, keepdims=True) - onehot)
                  * (sw / den)[:, None], dtype)
        grads = dict(W=_cast(_cast(X, mi).T @ _cast(d, mi)
                             + f32(2.0) * l2 * W, dtype),
                     b=_cast(d.sum(0), dtype))
        t = int(new["t"]) + 1
        new["t"] = np.int32(t)
        for q in ("W", "b"):
            g = grads[q]
            m = _cast(f32(0.9) * new["m" + q] + f32(0.1) * g, dtype)
            v = _cast(f32(0.999) * new["v" + q] + f32(0.001) * g * g, dtype)
            mh = m / (f32(1.0) - f32(0.9) ** f32(t))
            vh = v / (f32(1.0) - f32(0.999) ** f32(t))
            new[q] = _cast(new[q] - lr * mh / (np.sqrt(vh) + f32(1e-8)),
                           dtype)
            new["m" + q], new["v" + q] = m, v
    return new
