"""The comparison that decides ``correct`` for the served cells.

Two parts:

``check_answers`` holds the front end to its guarantees over every
request of the run: each request sent got one final answer (``done``)
within the drain limit; the answer the client read is the one the server
recorded for that request id, and the one the device tick finalized for
that request's (shard, uid), field by field; every answer keeps the
adaptive-redundancy guarantee (1 to ``votes_cap`` votes, and a confidence
at the threshold unless the cap was reached); the server's conservation
ledger balances with nothing left in it.

``check_ticks`` compares the serve ticks recorded after the window, from
their pre-tick state, against the plain reference of
``reference/crowd_tick.py``: every answer, the answer half of the
post-tick state and, with the learner on, the learner after the tick
(its replay ring, and its weights after the reference's own Adam steps on
that ring). Integers, masks and times must agree exactly; the posterior's
and the learner's floats to within the configuration's limits. A finalize or
argmax decision whose reference value lies within the configuration's
``decision_eps`` of its boundary follows the program, since rounding
alone may tip it.
"""
from __future__ import annotations

import numpy as np

from reference import crowd_tick, xlstm_encoder

# The limits live in each configuration's file (``limits``: the number's
# name and its largest allowed value; ``at_least`` names those that are
# floors instead), set from the readings PERF.md gives. An exact
# comparison has the limit 0.


def passed(numbers: dict, cfg: dict) -> bool:
    floors = set(cfg.get("at_least", ()))
    for k, lim in cfg["limits"].items():
        v = numbers.get(k)
        if v is None or not np.isfinite(v):
            return False
        if (v < lim) if k in floors else (v > lim):
            return False
    return True


def lines(numbers: dict, cfg: dict) -> dict:
    """Each number with its limit, for the result line and stderr."""
    floors = set(cfg.get("at_least", ()))
    return {k: {"value": numbers.get(k), "limit": lim,
                "rule": ">=" if k in floors else "<="}
            for k, lim in cfg["limits"].items()}


# ---------------------------------------------------------------------------
# front end
# ---------------------------------------------------------------------------

def fin_table(outs) -> dict:
    """(shard, uid) -> (label, votes, conf) for every slot any recorded
    tick finalized; a (shard, uid) finalized twice maps to None."""
    table: dict = {}
    for out in outs:
        for s, w in zip(*np.nonzero(out["fin"])):
            key = (int(s), int(out["uid"][s, w]))
            val = (int(out["label"][s, w]), int(out["votes"][s, w]),
                   float(out["conf"][s, w]))
            table[key] = None if key in table else val
    return table


def check_answers(p: dict, rec: dict, reqs: dict, outs, stats: dict) -> dict:
    """``rec`` is the generator's per-request record, ``reqs`` the
    server's request table (id -> request), ``outs`` every tick's output
    in order, ``stats`` the server's counters after the drain."""
    cap, thr, C = p["votes_cap"], p["conf_threshold"], p["n_classes"]
    table = fin_table(outs)
    unanswered = bad = 0
    seen = set()
    for i in range(len(rec["due"])):
        st, http = rec["status"][i], rec["http"][i]
        if http in (429, 503):
            continue                      # refused: failed, not wrong
        if http != 200 or st != "done":
            unanswered += 1
            continue
        rid = rec["id"][i]
        lab, conf, votes = rec["label"][i], rec["conf"][i], rec["votes"][i]
        req = reqs.get(rid)
        ok = rid not in seen and req is not None and req.status == "done"
        seen.add(rid)
        if ok:
            ok = (req.label == lab and req.votes == votes
                  and round(req.conf, 6) == conf)
            tick = table.get((req.shard, req.uid))
            ok = ok and tick is not None and tick[0] == lab \
                and tick[1] == votes and round(tick[2], 6) == conf
        ok = ok and 0 <= lab < C and 1 <= votes <= cap \
            and (conf >= thr - 1e-6 or votes == cap)
        bad += not ok
    ledger = stats["conservation"] and stats["pending"] == 0 \
        and stats["in_system"] == 0 \
        and stats["submitted"] == stats["answered"] + stats["dropped"]
    return dict(unanswered=unanswered, answer_mismatch=bad + (not ledger))


# ---------------------------------------------------------------------------
# ticks against the reference
# ---------------------------------------------------------------------------

_EXACT_WIN = ("active", "arrival_t", "uid", "true_label", "difficulty",
              "n_votes", "vote_wid", "vote_lab")
_EXACT_BL = ("head", "count", "times", "uid")
_EXACT_BL_LM = ("tlab", "diff", "feat")


def learner_view(state: dict) -> dict:
    """The learner's part of a (host) state: weights, Adam's moments and
    step, and the replay ring."""
    W, b, mW, mb, vW, vb, t = state["learn"]
    return dict(W=W, b=b, mW=mW, mb=mb, vW=vW, vb=vb, t=t,
                buf_X=state["buf_X"], buf_y=state["buf_y"],
                buf_n=state["buf_n"])


def learner_params(p: dict, pre: dict):
    """The fused learner's weights and fusion weight as the tick reads
    them from its state (None with the learner off)."""
    if not p.get("learner"):
        return None
    W, b = pre["learn"][0], pre["learn"][1]
    fuse_w = p["learner_prior_scale"] * min(
        1.0, float(pre["buf_n"]) / p["learner_ramp_n"])
    return dict(W=np.asarray(W, np.float32), b=np.asarray(b, np.float32),
                fuse_w=np.float32(fuse_w))


def reference_tick(p: dict, tick: dict, dtype: str, follow, eps: float,
                   follow_lrn=None):
    """The reference's answers, post-state and (with the learner on)
    learner after one recorded tick. ``follow`` (if not None) is the
    output judged, ``follow_lrn`` its learner: decisions within ``eps`` of
    their boundary take its value."""
    pre = tick["pre"]
    S = p["n_shards"]
    lrn = learner_params(p, pre)
    outs, posts = [], []
    tf, tl = [], []
    for s in range(S):
        feat = None if tick["feat"] is None else tick["feat"][s]
        labels = None if tick["labels"] is None else tick["labels"][s]
        o, po, mg = crowd_tick.shard_tick(
            p, pre, s, int(tick["n_arr"][s]), int(tick["uid_base"][s]),
            feat, labels, learner=lrn, dtype=dtype)
        fin, label = o["fin"], o["label"]
        if follow is not None:
            amb_f = mg["conf"] < eps
            amb_l = mg["label"] < eps
            fin = np.where(amb_f, follow["fin"][s], fin) \
                & po["win"]["active"]
            label = np.where(amb_l, follow["label"][s], label)
        t = np.float32(pre["t"])
        o["fin"], o["label"] = fin, label.astype(np.int32)
        o["tis"] = np.where(fin, t - po["win"]["arrival_t"], np.float32(0))
        if lrn is not None:
            # training pairs: finalized with a crowd vote, labeled by the
            # crowd-only posterior (a near tie follows the judged ring)
            tm = fin & (po["win"]["n_votes"] >= 1) \
                if p["learner_train_crowd_only"] else fin
            tf.append(po["win"]["feat"][tm])
            tl.append(po["win"]["logpost"].argmax(-1)[tm].astype(np.int32))
            tl[-1] = np.where(mg["crowd"][tm] < eps, -1, tl[-1])
        crowd_tick.finish_shard(p, po, fin, label, pre["t"])
        o["backlog"] = po["bl"]["count"]
        o["in_flight"] = np.int32(po["win"]["active"].sum())
        outs.append(o)
        posts.append(po)
    if lrn is None:
        return outs, posts, None
    tl = np.concatenate(tl)
    amb = np.nonzero(tl < 0)[0]
    if len(amb):
        if follow_lrn is None:
            tl[amb] = 0
        else:
            ring = (int(pre["buf_n"]) + amb) % p["learner_buffer"]
            tl[amb] = np.asarray(follow_lrn["buf_y"])[ring]
    new = crowd_tick.learner_update(p, learner_view(pre), int(pre["step"]),
                                    np.concatenate(tf), tl, dtype=dtype)
    return outs, posts, new


_EXACT_LRN = ("t", "buf_n", "buf_X", "buf_y")


def compare_tick(p: dict, ref_outs, ref_posts, ref_lrn, prog_out,
                 prog_post, prog_lrn) -> dict:
    """Mismatch count and float errors of one tick: the program's (or the
    control's) output, post-state and learner against the reference's."""
    fields: dict = {}
    conf_err, lp_err, answers, known = 0.0, 0.0, 0, 0
    lm = p["feature_kind"] == "lm"

    def count(name, a, b):
        n = int((np.asarray(a) != np.asarray(b)).sum())
        if n:
            fields[name] = fields.get(name, 0) + n

    for s, (o, po) in enumerate(zip(ref_outs, ref_posts)):
        for k in ("fin", "uid", "votes", "tis", "dropped", "backlog",
                  "in_flight"):
            count("out." + k, prog_out[k][s], o[k])
        fin = o["fin"]
        count("out.label", np.asarray(prog_out["label"][s])[fin],
              o["label"][fin])
        answers += int(fin.sum())
        known += int((fin & o["known"]).sum())
        conf_err = max(conf_err, float(np.abs(
            np.asarray(prog_out["conf"][s], np.float64) - o["conf"]).max()))
        for g, keys in (("win", _EXACT_WIN + (("feat",) if lm else ())),
                        ("bl", _EXACT_BL + (_EXACT_BL_LM if lm else ())),
                        ("ws", ("est_correct", "est_n"))):
            for k in keys:
                a, b = np.asarray(prog_post[g][k][s]), np.asarray(po[g][k])
                if g == "bl" and a.ndim:
                    # the ring's last row takes the masked-off writes
                    a, b = a[:p["backlog"]], b[:p["backlog"]]
                count(f"{g}.{k}", a, b)
        lp_err = max(lp_err, float(np.abs(
            np.asarray(prog_post["win"]["logpost"][s], np.float64)
            - po["win"]["logpost"]).max()))
    numbers = dict(conf_err=conf_err, logpost_err=lp_err,
                   checked_answers=answers, known_answers=known)
    if ref_lrn is not None:
        B = p["learner_buffer"]
        for k in _EXACT_LRN:
            a, b = np.asarray(prog_lrn[k]), np.asarray(ref_lrn[k])
            if a.ndim:
                # the ring's last row takes the masked-off writes
                a, b = a[:B], b[:B]
            count("learn." + k, a, b)
        numbers["learn_err"] = max(float(np.abs(
            np.asarray(prog_lrn[k], np.float64) - ref_lrn[k]).max())
            for k in ("W", "b"))
    return dict(numbers, tick_mismatch=sum(fields.values()),
                mismatch_fields=fields)


def _merge(acc: dict, d: dict):
    for k, v in d.items():
        if k == "mismatch_fields":
            for f, n in v.items():
                acc[k][f] = acc[k].get(f, 0) + n
        elif k in ("conf_err", "logpost_err", "learn_err"):
            acc[k] = max(acc.get(k, 0.0), v)
        else:
            acc[k] = acc.get(k, 0) + v


def check_ticks(p: dict, ticks, eps: float, control: str = None) -> dict:
    """The recorded ticks against the float32 reference: the program's
    output, post-state and learner, or with ``control`` (a dtype) the
    reference computed in that precision put in the program's place."""
    acc = dict(tick_mismatch=0, conf_err=0.0, logpost_err=0.0,
               checked_answers=0, checked_ticks=0, mismatch_fields={})
    for tick in ticks:
        got_out, got_post = tick["out"], tick["post"]
        got_lrn = learner_view(got_post) if p.get("learner") else None
        if control is not None:
            co, cp, got_lrn = reference_tick(p, tick, control, None, eps)
            got_out = {k: np.stack([np.asarray(o[k]) for o in co])
                       for k in co[0]}
            got_post = {g: {k: np.stack([pp[g][k] for pp in cp])
                            for k in cp[0][g]} for g in ("win", "bl", "ws")}
        ro, rp, rl = reference_tick(p, tick, "float32", got_out, eps,
                                    got_lrn)
        _merge(acc, compare_tick(p, ro, rp, rl, got_out, got_post,
                                 got_lrn))
        acc["checked_ticks"] += 1
    return acc


# ---------------------------------------------------------------------------
# text embeddings against the encoder reference
# ---------------------------------------------------------------------------

def embed_sample(enc: dict, calls, seed: int) -> dict:
    """Rows drawn from the seed among every text the window embedded, the
    longest text among them, with the program's features for each."""
    texts = [t for ts, _ in calls for t in ts]
    if not texts:
        return dict(texts=[], program=np.zeros((0, enc["n_features"])))
    feats = np.concatenate([np.asarray(f, np.float64) for _, f in calls])
    n = min(enc["sample_rows"], len(texts))
    longest = max(range(len(texts)), key=lambda i: len(texts[i].split()))
    rest = [i for i in range(len(texts)) if i != longest]
    rng = np.random.default_rng(seed)
    pick = [longest] + list(rng.choice(rest, n - 1, replace=False)) \
        if n > 1 else [longest]
    return dict(texts=[texts[i] for i in pick], program=feats[pick])


def embed_err(prog, ref) -> float:
    """Relative error of a block of features: ||prog - ref|| / ||ref||."""
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(np.asarray(prog, np.float64) - ref)
                 / max(np.linalg.norm(ref), 1e-30))


def check_embeddings(enc: dict, sample: dict, weight_seed: int,
                     dtype: str = "float32") -> dict:
    """The program's features (or, with ``dtype``, the reference's in that
    precision) against the float32 reference."""
    if not sample["texts"]:
        return dict(embed_rel_err=float("nan"), embed_rows=0)
    w = xlstm_encoder.weights(enc, weight_seed)
    ref = xlstm_encoder.features(enc, weight_seed, sample["texts"], w=w)
    sample["reference"] = ref
    got = sample["program"] if dtype == "float32" else \
        xlstm_encoder.features(enc, weight_seed, sample["texts"],
                               dtype=dtype, w=w)
    return dict(embed_rel_err=embed_err(got, ref),
                embed_rows=len(sample["texts"]))
