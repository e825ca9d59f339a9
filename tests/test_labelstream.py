"""labelstream subsystem validation: Dawid-Skene aggregation parity against
the scalar reference, the fused Pallas E-step kernel, arrival processes,
adaptive-redundancy policy, worker-aware routing (scored matching +
learner-driven backlog admission), and end-to-end streaming-service
invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quality import (
    em_worker_accuracy, em_worker_accuracy_ref, weighted_vote,
)
from repro.core.simfast import priority_match
from repro.labelstream import (
    ArrivalConfig, PolicyConfig, RoutingConfig, StreamConfig, dawid_skene,
    dawid_skene_batch, heterogeneous_stream_config, pack_votes, run_stream,
    scored_match, stream_summary,
)
from repro.labelstream.arrivals import init_arrival_state, sample_arrivals
from repro.labelstream.policy import should_finalize, target_outstanding
from repro.labelstream.router import _hist_percentile

# shared small config so the jit cache is warm across streaming tests
SCFG = StreamConfig(n_shards=2, pool_size=6, window=16, dt=5.0,
                    tis_bin_s=8.0,
                    arrivals=ArrivalConfig(kind="poisson", rate=0.012),
                    policy=PolicyConfig(adaptive=True, votes_cap=3,
                                        conf_threshold=0.95, min_votes=1,
                                        max_outstanding=1))
HORIZON = 700


def _synthetic_votes(n_tasks=30, accs=(0.95, 0.9, 0.85, 0.8, 0.3), seed=0,
                     n_classes=2):
    rng = np.random.default_rng(seed)
    truth = rng.integers(0, n_classes, n_tasks)
    tv = []
    for t in range(n_tasks):
        votes = []
        for w, a in enumerate(accs):
            if rng.random() < a:
                votes.append((int(truth[t]), w))
            else:
                wrong = int(rng.integers(0, n_classes - 1))
                votes.append((wrong + 1 if wrong >= truth[t] else wrong, w))
        tv.append(votes)
    return tv, truth


# ------------------------------------------------------ aggregation parity --

def test_one_coin_parity_with_scalar_reference():
    """Vectorized one-coin DS == the scalar dict EM to float tolerance,
    including a task with an empty vote list."""
    tv, truth = _synthetic_votes()
    tv.append([])                          # empty vote list must not crash
    l_ref, a_ref = em_worker_accuracy_ref(tv, 2)
    l_vec, a_vec = em_worker_accuracy(tv, 2)
    assert l_ref == l_vec
    for w in a_ref:
        assert abs(a_ref[w] - a_vec[w]) < 1e-4
    # the engine also identifies the adversarial worker
    assert a_vec[4] < 0.6 < a_vec[0]
    assert np.mean(np.array(l_vec[:-1]) == truth) >= 0.9


def test_one_coin_parity_three_classes():
    tv, _ = _synthetic_votes(n_tasks=24, seed=3, n_classes=3)
    l_ref, a_ref = em_worker_accuracy_ref(tv, 3)
    l_vec, a_vec = em_worker_accuracy(tv, 3)
    assert l_ref == l_vec
    for w in a_ref:
        assert abs(a_ref[w] - a_vec[w]) < 1e-4


def test_full_confusion_captures_class_bias():
    """A worker who always answers 0 is useless symmetrically but perfectly
    informative per-class; the full-confusion model sees the asymmetry."""
    rng = np.random.default_rng(1)
    truth = rng.integers(0, 2, 60)
    tv = []
    for t in range(60):
        votes = [(int(truth[t]) if rng.random() < 0.9
                  else 1 - int(truth[t]), w) for w in range(3)]
        votes.append((0, 99))              # the always-0 worker
        tv.append(votes)
    pack, n_workers = pack_votes(tv)
    out = dawid_skene(pack.labels, pack.workers, pack.mask,
                      n_workers=n_workers, n_classes=2, one_coin=False)
    conf = np.asarray(out["confusion"])
    bias_idx = pack.worker_ids.index(99)
    # votes 0 with probability ~1 regardless of the true class
    assert conf[bias_idx, 0, 0] > 0.9
    assert conf[bias_idx, 1, 0] > 0.9
    labels = np.asarray(out["posterior"])[:60].argmax(-1)
    assert (labels == truth).mean() >= 0.9


def test_dawid_skene_batch_matches_single():
    tv, _ = _synthetic_votes(n_tasks=16, seed=5)
    pack, n_workers = pack_votes(tv)
    reps = 3
    stack = lambda a: np.broadcast_to(a, (reps,) + a.shape)
    out_b = dawid_skene_batch(stack(pack.labels), stack(pack.workers),
                              stack(pack.mask), n_workers=n_workers,
                              n_classes=2)
    out_1 = dawid_skene(pack.labels, pack.workers, pack.mask,
                        n_workers=n_workers, n_classes=2)
    for r in range(reps):
        np.testing.assert_allclose(np.asarray(out_b["posterior"])[r],
                                   np.asarray(out_1["posterior"]), atol=1e-6)


def test_ds_estep_kernel_matches_ref():
    from repro.kernels import ref
    from repro.kernels.ds_estep import ds_estep
    rng = np.random.default_rng(0)
    W, C, T, V = 9, 4, 77, 5
    R = W * C + 1
    rows = np.log(rng.uniform(0.05, 0.95, (R, C))).astype(np.float32)
    rows[-1] = 0.0
    idx = rng.integers(0, R, (T, V)).astype(np.int32)
    idx[7] = R - 1                         # zero-vote task
    logp, post = ds_estep(jnp.array(rows), jnp.array(idx), interpret=True)
    logp_r, post_r = ref.ds_estep_ref(jnp.array(rows), jnp.array(idx))
    np.testing.assert_allclose(np.asarray(logp), np.asarray(logp_r),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(post), np.asarray(post_r),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(post)[7], 0.25, atol=1e-6)


@pytest.mark.parametrize("W,C,T,V", [(9, 200, 77, 3), (9, 4, 300, 5),
                                     (3, 2, 32, 5), (129, 200, 40, 3)])
def test_ds_estep_kernel_gathers_rows_at_any_width(W, C, T, V):
    """The row-gathering E-step against its oracle at 200 classes and at
    the widths above, one table and a batch of tables under ``vmap``
    (padded, null and repeated votes included)."""
    from repro.kernels import ref
    from repro.kernels.ds_estep import ds_estep
    rng = np.random.default_rng(W * C + T)
    R = W * C + 1
    rows = np.log(rng.uniform(0.05, 0.95, (3, R, C))).astype(np.float32)
    rows[:, -1] = 0.0
    idx = rng.integers(0, R, (3, T, V)).astype(np.int32)
    idx[:, 5] = R - 1                      # zero-vote task
    idx[:, 6] = idx[:, 6, :1]              # one row named by every vote
    logp, post = ds_estep(jnp.array(rows[0]), jnp.array(idx[0]),
                          interpret=True)
    logp_r, post_r = ref.ds_estep_ref(jnp.array(rows[0]),
                                      jnp.array(idx[0]))
    np.testing.assert_allclose(np.asarray(logp), np.asarray(logp_r),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(post), np.asarray(post_r),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(post)[5], 1.0 / C, atol=1e-6)
    bl, bp = jax.vmap(lambda r, i: ds_estep(r, i, interpret=True))(
        jnp.array(rows), jnp.array(idx))
    rl, rp = jax.vmap(ref.ds_estep_ref)(jnp.array(rows), jnp.array(idx))
    np.testing.assert_allclose(np.asarray(bl), np.asarray(rl), atol=1e-4)
    np.testing.assert_allclose(np.asarray(bp), np.asarray(rp), atol=1e-5)


def _one_coin_votes(n_tasks, n_workers, n_classes, acc, votes, seed):
    """Each task voted by ``votes`` distinct workers of a one-coin crowd
    of accuracy ``acc``; wrong votes uniform over the other classes."""
    rng = np.random.default_rng(seed)
    truth = rng.integers(0, n_classes, n_tasks)
    labels = np.zeros((n_tasks, votes), np.int32)
    workers = np.zeros((n_tasks, votes), np.int32)
    for t in range(n_tasks):
        workers[t] = rng.choice(n_workers, votes, replace=False)
        for j in range(votes):
            if rng.random() < acc:
                labels[t, j] = truth[t]
            else:
                w = int(rng.integers(0, n_classes - 1))
                labels[t, j] = w + 1 if w >= truth[t] else w
    return labels, workers, np.ones((n_tasks, votes), bool), truth


def test_full_confusion_accuracy_at_200_classes():
    """A one-coin crowd of accuracy 0.9 over 200 classes: the full-
    confusion EM's accuracies come out near 0.9 and near each worker's
    share of right votes, not near the mean of its confusion's diagonal,
    whose rows of unseen classes stay uniform at 1/C."""
    labels, workers, mask, truth = _one_coin_votes(600, 20, 200, 0.9, 3,
                                                   seed=1)
    out = dawid_skene(labels, workers, mask, n_workers=20, n_classes=200,
                      iters=8)
    acc = np.asarray(out["accuracy"])
    right = labels == truth[:, None]
    # each worker's share of right votes, with the EM's smoothing
    seen = np.array([(right[workers == w].sum() + 1 / 200)
                     / ((workers == w).sum() + 1) for w in range(20)])
    assert abs(acc.mean() - 0.9) < 0.03, acc
    np.testing.assert_allclose(acc, seen, atol=0.03)
    conf = np.asarray(out["confusion"])
    # each worker saw under half of the classes: the diagonal's mean
    # would read below 0.5
    assert np.einsum("wcc->w", conf).mean() / 200 < 0.5


def test_full_confusion_accuracy_at_two_classes_matches_diagonal_mean():
    """At two balanced classes the diagonal share of a worker's votes
    stays within 0.02 of the mean of its confusion's diagonal."""
    labels, workers, mask, _ = _one_coin_votes(800, 10, 2, 0.85, 3, seed=2)
    out = dawid_skene(labels, workers, mask, n_workers=10, n_classes=2,
                      iters=8)
    diag_mean = np.einsum("wcc->w", np.asarray(out["confusion"])) / 2
    np.testing.assert_allclose(np.asarray(out["accuracy"]), diag_mean,
                               atol=0.02)


def test_ds_em_with_kernel_estep_matches_jnp_path():
    tv, _ = _synthetic_votes(n_tasks=20, seed=7)
    pack, n_workers = pack_votes(tv)
    kw = dict(n_workers=n_workers, n_classes=2, iters=8, one_coin=True)
    out_k = dawid_skene(pack.labels, pack.workers, pack.mask,
                        use_kernel=True, **kw)
    out_j = dawid_skene(pack.labels, pack.workers, pack.mask,
                        use_kernel=False, **kw)
    np.testing.assert_allclose(np.asarray(out_k["posterior"]),
                               np.asarray(out_j["posterior"]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_k["accuracy"]),
                               np.asarray(out_j["accuracy"]), atol=1e-4)


def test_weighted_vote_boundary_accuracies():
    """Unanimous windows can push EM estimates to 0/1; the log-odds weights
    must stay finite and the vote well-defined."""
    votes = [(0, 1, 5.0), (0, 2, 5.0), (1, 3, 5.0)]
    assert weighted_vote(votes, 2, {1: 1.0, 2: 1.0, 3: 0.0}) in (0, 1)
    assert weighted_vote([], 2, {}) == 0


# ------------------------------------------------------------- arrivals ----

def test_poisson_arrival_mean():
    cfg = ArrivalConfig(kind="poisson", rate=0.5)
    state = init_arrival_state(cfg)
    keys = jax.random.split(jax.random.key(0), 400)
    ns = [int(sample_arrivals(cfg, state, k, 0.0, 10.0)[0]) for k in keys]
    assert abs(np.mean(ns) - 5.0) < 0.5    # Poisson(5)


def test_diurnal_rate_modulates():
    cfg = ArrivalConfig(kind="diurnal", rate=1.0, amplitude=0.8,
                        period_s=86400.0)
    state = init_arrival_state(cfg)
    from repro.labelstream.arrivals import rate_at
    peak = float(rate_at(cfg, state, 86400.0 / 4))
    trough = float(rate_at(cfg, state, 3 * 86400.0 / 4))
    assert peak == pytest.approx(1.8, abs=1e-6)
    assert trough == pytest.approx(0.2, abs=1e-6)


def test_mmpp_visits_both_modes():
    cfg = ArrivalConfig(kind="mmpp", rate=0.1, rate_hi=2.0,
                        dwell_mean_s=50.0)
    state = init_arrival_state(cfg)
    key = jax.random.key(0)
    modes = []
    for i in range(300):
        key, k = jax.random.split(key)
        _, state, rate = sample_arrivals(cfg, state, k, i * 10.0, 10.0)
        modes.append(int(state["mode"]))
    assert 0.1 < np.mean(modes) < 0.9      # both states visited


# --------------------------------------------------------------- policy ----

def test_fixed_policy_finalizes_exactly_at_cap():
    pol = PolicyConfig(adaptive=False, votes_cap=3)
    lp = jnp.zeros((4, 2))
    nv = jnp.array([0, 1, 2, 3])
    fin, _ = should_finalize(lp, nv, pol)
    assert np.asarray(fin).tolist() == [False, False, False, True]
    assert np.asarray(target_outstanding(nv, pol)).tolist() == [3, 2, 1, 0]


def test_adaptive_policy_confident_early_stop():
    pol = PolicyConfig(adaptive=True, votes_cap=5, conf_threshold=0.9,
                       min_votes=2, max_outstanding=1)
    confident = jnp.array([[0.0, 4.0]])
    uncertain = jnp.array([[0.0, 0.3]])
    fin_c, conf_c = should_finalize(confident, jnp.array([2]), pol)
    fin_u, _ = should_finalize(uncertain, jnp.array([2]), pol)
    fin_few, _ = should_finalize(confident, jnp.array([1]), pol)
    assert bool(fin_c[0]) and float(conf_c[0]) > 0.9
    assert not bool(fin_u[0])
    assert not bool(fin_few[0])            # min_votes gate
    # the cap always finalizes, confident or not
    fin_cap, _ = should_finalize(uncertain, jnp.array([5]), pol)
    assert bool(fin_cap[0])
    # outstanding never exceeds the remaining budget
    assert np.asarray(target_outstanding(jnp.array([4, 5]), pol)).tolist() \
        == [1, 0]


# ---------------------------------------------------- streaming service ----

def test_stream_conservation_and_quality():
    """Every arrival is exactly one of: dropped, backlogged, in flight, or
    finalized; votes stay under the cap; labels are accurate."""
    out = run_stream(SCFG, HORIZON, n_reps=2, seed=0)
    arrived = int(np.asarray(out["arrived"]).sum())
    done = int(np.asarray(out["done_all"]).sum())
    backlog = int(np.asarray(out["backlog_end"]).sum())
    in_flight = int(np.asarray(out["in_flight_end"]).sum())
    dropped = int(np.asarray(out["dropped"]).sum())
    assert arrived == done + backlog + in_flight + dropped
    s = stream_summary(SCFG, out)
    assert s["sustained_rate"] > 0
    assert s["accuracy"] > 0.85
    assert 0 < s["votes_per_task"] <= SCFG.policy.votes_cap + 1e-6
    assert s["p95_tis"] < 1500.0


def test_stream_determinism():
    a = run_stream(SCFG, HORIZON, n_reps=2, seed=11)
    b = run_stream(SCFG, HORIZON, n_reps=2, seed=11)
    np.testing.assert_array_equal(np.asarray(a["hist"]),
                                  np.asarray(b["hist"]))
    assert int(np.asarray(a["done"]).sum()) == int(np.asarray(b["done"]).sum())


def test_streaming_beats_batch_replay_tail_latency():
    """Same offered load, same pools: continuous admission holds p95
    time-in-system far below the drain-then-refill batch baseline."""
    naive = dataclasses.replace(
        SCFG, batch_replay=True, straggler=False,
        policy=PolicyConfig(adaptive=False, votes_cap=3))
    s_stream = stream_summary(
        SCFG, run_stream(SCFG, HORIZON, n_reps=2, seed=2))
    s_naive = stream_summary(
        naive, run_stream(naive, HORIZON, n_reps=2, seed=2))
    assert s_stream["p95_tis"] < 0.5 * s_naive["p95_tis"]
    assert s_stream["p50_tis"] < 0.5 * s_naive["p50_tis"]


def test_adaptive_redundancy_saves_votes_at_matched_accuracy():
    """Skewed-difficulty workload: posterior-confidence stopping spends
    fewer votes than fixed redundancy without giving up accuracy."""
    fixed = dataclasses.replace(
        SCFG, p_hard=0.25, hard_scale=0.3,
        policy=PolicyConfig(adaptive=False, votes_cap=5))
    adapt = dataclasses.replace(
        SCFG, p_hard=0.25, hard_scale=0.3,
        policy=PolicyConfig(adaptive=True, votes_cap=5, conf_threshold=0.98,
                            min_votes=2, max_outstanding=2))
    s_f = stream_summary(fixed, run_stream(fixed, HORIZON, n_reps=2, seed=3,
                                           rate_scale=0.75))
    s_a = stream_summary(adapt, run_stream(adapt, HORIZON, n_reps=2, seed=3,
                                           rate_scale=0.75))
    assert s_a["votes_per_task"] <= 0.8 * s_f["votes_per_task"]
    assert s_a["accuracy"] >= s_f["accuracy"] - 0.05


def test_online_posterior_consistent_with_offline_em():
    """The stream's online one-coin posterior (incremental E-step + hard-EM
    voter crediting) must not LOSE accuracy against the exact offline
    full-confusion EM given an equivalent vote budget from the same worker
    population — the online path is an approximation of the offline
    engine, not a weaker estimator. (It may come out a little higher: the
    adaptive policy finalizes early only when confident and spends extra
    votes on the hard tasks, a selection effect the flat offline replay
    does not have.)"""
    from repro.labelstream.aggregate import aggregate_votes
    out = run_stream(SCFG, HORIZON, n_reps=4, seed=6)
    s = stream_summary(SCFG, out)
    # offline: same Beta(18,2)-clipped accuracy population, matched votes
    rng = np.random.default_rng(6)
    n_tasks, n_votes = 300, max(2, round(s["votes_per_task"]))
    accs = np.clip(rng.beta(SCFG.acc_a, SCFG.acc_b, 24), 0.55, 0.995)
    truth = rng.integers(0, 2, n_tasks)
    tv = []
    for t in range(n_tasks):
        ws = rng.choice(len(accs), n_votes, replace=False)
        tv.append([(int(truth[t] if rng.random() < accs[w]
                        else 1 - truth[t]), int(w)) for w in ws])
    labels, _, _ = aggregate_votes(tv, 2, one_coin=False)
    offline_acc = (np.array(labels) == truth).mean()
    assert s["accuracy"] >= offline_acc - 0.05, \
        (s["accuracy"], offline_acc)


# ------------------------------------------------- worker-aware routing ----

# the canonical heterogeneous worker pool (wide Beta accuracy spread, weak
# estimation prior, long sessions so the online estimates mature) where
# worker-aware routing has real signal to exploit — the SAME workload bench
# section 5 gates and the demo shows; shared across the routing tests so
# the jit cache is warm
HET = heterogeneous_stream_config()
HET_AWARE = dataclasses.replace(HET, routing=RoutingConfig(enabled=True))


def test_scored_match_uniform_parity():
    """ISSUE-4 safety net: the worker-aware matcher with UNIFORM scores is
    bit-for-bit `priority_match` across seeded random pool/window states —
    take mask, matched tasks, tier-1 membership and tier-1 count all
    identical, so the scored path provably generalizes the two-tier
    uniform match instead of forking it."""
    rng = np.random.default_rng(1234)
    P, B = 8, 32
    for const in (0.0, 1.7, -3.2):
        for _ in range(100):
            avail = jnp.asarray(rng.random(P) < rng.uniform(0.2, 0.9))
            t1 = rng.random(B) < rng.uniform(0.1, 0.6)
            t2 = (rng.random(B) < rng.uniform(0.1, 0.6)) & ~t1
            t1, t2 = jnp.asarray(t1), jnp.asarray(t2)
            shift = jnp.int32(rng.integers(0, B))
            take_r, task_r, tier1_r, n1_r = priority_match(
                avail, t1, t2, shift)
            take_s, task_s, tier1_s, n1_s = scored_match(
                jnp.full((P, B), const), avail, t1, t2, shift)
            np.testing.assert_array_equal(np.asarray(take_r),
                                          np.asarray(take_s))
            tk = np.asarray(take_r)
            np.testing.assert_array_equal(np.asarray(task_r)[tk],
                                          np.asarray(task_s)[tk])
            np.testing.assert_array_equal(np.asarray(tier1_r),
                                          np.asarray(tier1_s))
            assert int(n1_r) == int(n1_s)


def test_routing_uniform_scores_stream_parity():
    """End-to-end flavor of the same safety net: a stream with routing
    ENABLED but zero score weights (uniform score matrix) is bit-for-bit
    the stream with routing disabled — histogram and every counter."""
    zero = dataclasses.replace(
        HET, routing=RoutingConfig(enabled=True, w_acc=0.0, w_speed=0.0))
    a = run_stream(HET, 400, n_reps=2, seed=3)
    b = run_stream(zero, 400, n_reps=2, seed=3)
    np.testing.assert_array_equal(np.asarray(a["hist"]),
                                  np.asarray(b["hist"]))
    for k in ("done", "correct", "votes_fin", "done_all", "dropped"):
        assert int(np.asarray(a[k]).sum()) == int(np.asarray(b[k]).sum()), k


def test_worker_aware_routing_saves_votes_heterogeneous_pool():
    """ISSUE-4 acceptance: on a heterogeneous pool, FROG-style scored
    matching (accurate workers to uncertain tasks, fast workers to easy
    ones, low-value workers idle when vote demand is scarce) spends
    markedly fewer votes than the uniform two-tier match at matched-or-
    better accuracy. Measured at this seed: ~35% fewer votes, +4pp
    accuracy, lower p95 — asserted with wide margins."""
    s_u = stream_summary(HET, run_stream(HET, 1200, n_reps=3, seed=5))
    s_a = stream_summary(HET_AWARE,
                         run_stream(HET_AWARE, 1200, n_reps=3, seed=5))
    assert s_a["votes_per_task"] <= 0.85 * s_u["votes_per_task"], \
        (s_a["votes_per_task"], s_u["votes_per_task"])
    assert s_a["accuracy"] >= s_u["accuracy"] - 0.02, \
        (s_a["accuracy"], s_u["accuracy"])
    assert s_a["p95_tis"] <= 1.1 * s_u["p95_tis"], \
        (s_a["p95_tis"], s_u["p95_tis"])


def test_routing_stream_determinism():
    """Scored matching + uncertain admission + learner fusion: same seed,
    same stream, twice."""
    from repro.labelstream import StreamLearnerConfig
    cfg = dataclasses.replace(
        HET, learner=StreamLearnerConfig(enabled=True, min_votes_known=1),
        routing=RoutingConfig(enabled=True, admission="uncertain"))
    a = run_stream(cfg, 400, n_reps=2, seed=13)
    b = run_stream(cfg, 400, n_reps=2, seed=13)
    np.testing.assert_array_equal(np.asarray(a["hist"]),
                                  np.asarray(b["hist"]))
    assert int(np.asarray(a["votes_fin"]).sum()) \
        == int(np.asarray(b["votes_fin"]).sum())


def test_uncertain_admission_conservation_under_burst():
    """Learner-driven most-uncertain-first admission must conserve tasks
    exactly like the FIFO ring — every arrival is dropped, backlogged, in
    flight, or finalized — including under bursty congestion where the
    backlog actually reorders."""
    from repro.labelstream import StreamLearnerConfig
    cfg = dataclasses.replace(
        HET, window=8,
        arrivals=ArrivalConfig(kind="mmpp", rate=0.01, rate_hi=0.12,
                               dwell_mean_s=900.0),
        learner=StreamLearnerConfig(enabled=True, min_votes_known=0),
        routing=RoutingConfig(enabled=True, admission="uncertain"))
    out = run_stream(cfg, 800, n_reps=2, seed=1)
    arrived = int(np.asarray(out["arrived"]).sum())
    done = int(np.asarray(out["done_all"]).sum())
    backlog = int(np.asarray(out["backlog_end"]).sum())
    in_flight = int(np.asarray(out["in_flight_end"]).sum())
    dropped = int(np.asarray(out["dropped"]).sum())
    assert arrived == done + backlog + in_flight + dropped
    s = stream_summary(cfg, out)
    assert s["accuracy"] > 0.7
    assert s["sustained_rate"] > 0


def test_uncertain_admission_requires_learner():
    cfg = dataclasses.replace(
        SCFG, routing=RoutingConfig(admission="uncertain"))
    with pytest.raises(ValueError, match="uncertain"):
        run_stream(cfg, 10, n_reps=1, seed=0)
    bad = dataclasses.replace(
        SCFG, routing=RoutingConfig(admission="lifo"))
    with pytest.raises(ValueError, match="admission"):
        run_stream(bad, 10, n_reps=1, seed=0)


def test_hist_percentile_empty_histogram():
    """Satellite fix: an empty time-in-system histogram (warmup, total
    overload) must report an infinite percentile, never NaN — NaN poisons
    downstream comparisons silently."""
    p = _hist_percentile(np.zeros(64, np.int64), 95, 4.0)
    assert p == float("inf") and not np.isnan(p)
    assert _hist_percentile(np.zeros(0, np.int64), 50, 4.0) == float("inf")
    # sanity on a non-empty histogram: right-edge percentile, finite
    h = np.zeros(64, np.int64)
    h[2] = 10
    assert _hist_percentile(h, 95, 4.0) == pytest.approx(12.0)
    # and a warmup-empty stream summary carries inf, not NaN
    out = run_stream(SCFG, 12, n_reps=1, seed=0, warmup_frac=1.0)
    s = stream_summary(SCFG, out)
    assert s["p95_tis"] == float("inf")


@pytest.mark.slow
def test_routing_soak_steady_state():
    """Long-horizon soak with worker-aware routing enabled: sustained
    throughput tracks offered load, backlog stays bounded, accuracy
    holds — routing must not destabilize the service."""
    out = run_stream(HET_AWARE, 10_000, n_reps=2, seed=4)
    s = stream_summary(HET_AWARE, out)
    assert s["sustained_rate"] >= 0.95 * s["offered_rate"]
    assert s["backlog_end"] < 3 * HET_AWARE.window
    assert s["dropped"] == 0
    assert s["accuracy"] > 0.75


@pytest.mark.slow
def test_stream_soak_steady_state():
    """Long-horizon soak: sustained throughput tracks offered load and the
    backlog stays bounded (no slow leak) over ~14 simulated hours."""
    out = run_stream(SCFG, 10_000, n_reps=2, seed=4)
    s = stream_summary(SCFG, out)
    assert s["sustained_rate"] >= 0.95 * s["offered_rate"]
    assert s["backlog_end"] < 3 * SCFG.window
    assert s["dropped"] == 0
    assert s["accuracy"] > 0.9


# --------------------------------------------- streaming hybrid learner ----

def _skewed(policy=None, **kw):
    return dataclasses.replace(
        SCFG, p_hard=0.25, hard_scale=0.3,
        policy=policy or PolicyConfig(adaptive=True, votes_cap=5,
                                      conf_threshold=0.98, min_votes=2,
                                      max_outstanding=2), **kw)


def test_learner_fused_redundancy_saves_votes_at_matched_accuracy():
    """ISSUE-3 acceptance: fusing the streaming learner's posterior into
    the DS posterior (stop-soliciting on model-known tasks) reaches
    matched accuracy with FEWER votes than DS-only adaptive redundancy."""
    from repro.labelstream import StreamLearnerConfig
    ds_only = _skewed()
    fused = _skewed(learner=StreamLearnerConfig(enabled=True,
                                                min_votes_known=1))
    # two horizons: ~130 finalized tasks, so one task is under the
    # 0.02 accuracy margin
    s_ds = stream_summary(ds_only, run_stream(ds_only, 2 * HORIZON,
                                              n_reps=2, seed=5))
    s_lf = stream_summary(fused, run_stream(fused, 2 * HORIZON, n_reps=2,
                                            seed=5))
    assert s_lf["votes_per_task"] <= 0.9 * s_ds["votes_per_task"], \
        (s_lf["votes_per_task"], s_ds["votes_per_task"])
    assert s_lf["accuracy"] >= s_ds["accuracy"] - 0.02, \
        (s_lf["accuracy"], s_ds["accuracy"])
    # the learner actually decided tasks (model-known finalizations)
    assert s_lf["model_known_frac"] > 0.2
    assert s_ds["model_known_frac"] == 0.0


def test_learner_stream_determinism():
    from repro.labelstream import StreamLearnerConfig
    cfg = _skewed(learner=StreamLearnerConfig(enabled=True))
    a = run_stream(cfg, 400, n_reps=2, seed=9)
    b = run_stream(cfg, 400, n_reps=2, seed=9)
    np.testing.assert_array_equal(np.asarray(a["hist"]),
                                  np.asarray(b["hist"]))
    assert int(np.asarray(a["model_known"]).sum()) \
        == int(np.asarray(b["model_known"]).sum())


def test_learner_feature_dim_validated():
    from repro.labelstream import StreamLearnerConfig
    cfg = dataclasses.replace(
        SCFG, n_classes=4,
        learner=StreamLearnerConfig(enabled=True, n_features=2))
    with pytest.raises(ValueError, match="n_features"):
        run_stream(cfg, 10, n_reps=1, seed=0)


def test_offline_ds_refresh_keeps_quality():
    """Satellite: the periodic offline full-confusion EM refresh re-runs
    aggregate.dawid_skene on the window vote log and resets online
    posteriors — the conservation invariant and label accuracy must hold,
    and the refreshed run must stay deterministic."""
    cfg = dataclasses.replace(_skewed(), refresh_every=40, refresh_iters=6)
    out = run_stream(cfg, HORIZON, n_reps=2, seed=7)
    arrived = int(np.asarray(out["arrived"]).sum())
    done = int(np.asarray(out["done_all"]).sum())
    backlog = int(np.asarray(out["backlog_end"]).sum())
    in_flight = int(np.asarray(out["in_flight_end"]).sum())
    dropped = int(np.asarray(out["dropped"]).sum())
    assert arrived == done + backlog + in_flight + dropped
    s = stream_summary(cfg, out)
    base = stream_summary(_skewed(), run_stream(_skewed(), HORIZON,
                                                n_reps=2, seed=7))
    assert s["accuracy"] >= base["accuracy"] - 0.05
    assert s["sustained_rate"] > 0
    out2 = run_stream(cfg, HORIZON, n_reps=2, seed=7)
    np.testing.assert_array_equal(np.asarray(out["hist"]),
                                  np.asarray(out2["hist"]))


# ------------------------------------------- serve tick phase named scopes --

# every phase of the serve tick runs in this scenario: cross-shard stealing,
# the learner, and the offline EM refresh every other tick
SCOPED_SERVE = ("stream_sharded", {"policy.learner.enabled": True,
                                   "policy.learner.refresh_every": 2})
TICK_SCOPES = ("admission", "votes", "refresh", "fusion", "finalize",
               "credit", "bookkeeping", "maintenance", "assign", "steal",
               "learner")


@pytest.fixture(scope="module")
def scoped_serve_cfg():
    from repro.scenarios import get_scenario
    from repro.scenarios.compile import to_serve_config
    return to_serve_config(get_scenario(*SCOPED_SERVE))


def test_serve_tick_lowering_names_every_phase_scope(scoped_serve_cfg):
    """Each phase of the tick carries its ``jax.named_scope`` into the
    ops' location metadata (``vmap(<scope>)`` inside the per-shard vmap),
    which is what a device trace and the compiled HLO's ``op_name`` name
    the phase by."""
    import re

    from repro.labelstream.router import _serve_tick_jit, serve_init

    cfg = scoped_serve_cfg
    inj = np.zeros((2, cfg.n_shards), np.int32)
    text = _serve_tick_jit.lower(cfg, serve_init(cfg, 0), inj, None, None,
                                 None).as_text(debug_info=True)
    missing = [s for s in TICK_SCOPES
               if not re.search(rf"[/(]{s}[)/]", text)]
    assert not missing, missing


def test_scoped_serve_tick_outputs_match_pinned_run(scoped_serve_cfg):
    """Named scopes change metadata only: 40 ticks of the scenario give
    the outputs and end state pinned from the program before the scopes
    were added. Re-pinned when the refresh's full-confusion accuracy
    became the diagonal share of the worker's votes (with the former
    accuracy that program read the former pin, 112d90ffa79db94d) and the
    M-step came to keep only the confusion rows the votes name: every
    integer and mask the same as the dense M-step's, floats within 5e-7."""
    import hashlib

    from repro.labelstream.router import serve_init, serve_tick

    cfg = scoped_serve_cfg
    S = cfg.n_shards
    state = serve_init(cfg, seed=0)
    h, base, fin = hashlib.sha256(), np.zeros((S,), np.int64), 0
    for i in range(40):
        n = np.asarray([(i + s) % 3 for s in range(S)], np.int32)
        state, out = serve_tick(cfg, state, n, base.astype(np.int32))
        base += n
        out = jax.device_get(out)
        fin += int(out["fin"].sum())
        for k in sorted(out):
            h.update(np.ascontiguousarray(out[k]).tobytes())
    for x in jax.tree_util.tree_leaves(jax.device_get(state)):
        h.update(np.ascontiguousarray(x).tobytes())
    assert fin == 44
    assert h.hexdigest()[:16] == "038eeb20b612916a"
