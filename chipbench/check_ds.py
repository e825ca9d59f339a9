"""The tick comparison that decides ``correct`` for the served cells with
the offline Dawid-Skene refresh on.

``check.check_ticks`` with ``reference/crowd_tick_ds.py`` in place of
``reference/crowd_tick.py``: every recorded tick, from its own pre-tick
state, against the numpy reference, the refresh included on the ticks
that run it. Integers, masks and times must agree exactly, as there; the
floats to within the configuration's limits: the answers' confidence
(``conf_err``), the window's log-posteriors (``logpost_err``) and, since
a refresh sets them from float EM, each worker's ``est_correct``
(``est_err``; ``est_n`` stays exact). ``checked_refreshes`` counts the
checked ticks that ran the refresh, and ``refresh_votes`` the votes those
refreshes re-explained (all shards), as the reference counts them. A finalize or argmax decision within
``decision_eps`` of its boundary follows the program.
"""
from __future__ import annotations

import numpy as np

import check
from reference import crowd_tick, crowd_tick_ds


def reference_tick(p: dict, tick: dict, dtype: str, follow, eps: float):
    """The reference's answers and post-state after one recorded tick.
    ``follow`` (if not None) is the output judged: decisions within
    ``eps`` of their boundary take its value."""
    pre = tick["pre"]
    t = np.float32(pre["t"])
    outs, posts = [], []
    for s in range(p["n_shards"]):
        o, po, mg = crowd_tick_ds.shard_tick(
            p, pre, s, int(tick["n_arr"][s]), int(tick["uid_base"][s]),
            dtype=dtype)
        fin, label = o["fin"], o["label"]
        if follow is not None:
            fin = np.where(mg["conf"] < eps, follow["fin"][s], fin) \
                & po["win"]["active"]
            label = np.where(mg["label"] < eps, follow["label"][s], label)
        o["fin"], o["label"] = fin, label.astype(np.int32)
        o["tis"] = np.where(fin, t - po["win"]["arrival_t"], np.float32(0))
        crowd_tick.finish_shard(p, po, fin, label, pre["t"])
        o["backlog"] = po["bl"]["count"]
        o["in_flight"] = np.int32(po["win"]["active"].sum())
        outs.append(o)
        posts.append(po)
    return outs, posts


def compare_tick(p: dict, ref_outs, ref_posts, prog_out, prog_post) -> dict:
    """``check.compare_tick``'s numbers for one tick, with ``est_correct``
    held to ``est_err`` instead of exactly."""
    est = np.stack([po["ws"]["est_correct"] for po in ref_posts])
    got = np.asarray(prog_post["ws"]["est_correct"], np.float64)
    numbers = check.compare_tick(
        p, ref_outs, ref_posts, None, prog_out,
        dict(prog_post, ws=dict(prog_post["ws"], est_correct=est)), None)
    numbers["est_err"] = float(np.abs(got - est).max())
    return numbers


def check_ticks(p: dict, ticks, eps: float, control: str = None) -> dict:
    """The recorded ticks against the float32 reference: the program's
    output and post-state, or with ``control`` (a dtype) the reference
    computed in that precision put in the program's place."""
    acc = dict(tick_mismatch=0, conf_err=0.0, logpost_err=0.0, est_err=0.0,
               checked_answers=0, checked_ticks=0, checked_refreshes=0,
               refresh_votes=0, mismatch_fields={})
    for tick in ticks:
        got_out, got_post = tick["out"], tick["post"]
        if control is not None:
            co, cp = reference_tick(p, tick, control, None, eps)
            got_out = {k: np.stack([np.asarray(o[k]) for o in co])
                       for k in co[0]}
            got_post = {g: {k: np.stack([pp[g][k] for pp in cp])
                            for k in cp[0][g]} for g in ("win", "bl", "ws")}
        ro, rp = reference_tick(p, tick, "float32", got_out, eps)
        numbers = compare_tick(p, ro, rp, got_out, got_post)
        acc["est_err"] = max(acc["est_err"], numbers.pop("est_err"))
        check._merge(acc, numbers)
        acc["checked_ticks"] += 1
        acc["checked_refreshes"] += int(rp[0]["refreshed"])
        acc["refresh_votes"] += sum(po["refresh_votes"] for po in rp)
    return acc
