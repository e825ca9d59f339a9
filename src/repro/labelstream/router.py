"""Streaming router: ring-buffer task window over sharded retainer pools.

The batch engines (events.py, simfast.py) drain a finite task list; this
module is the open-world service: tasks arrive continuously (arrivals.py),
are queued in a per-shard backlog FIFO, admitted into a fixed-size
*ring-buffer task window* of ``window`` slots per shard, labeled by that
shard's retainer pool, and finalized by the adaptive-redundancy policy
(policy.py) on their running Dawid-Skene posterior. Per-tick cost is
O(shards * (pool + window)) — independent of how many tasks have flowed
through the system, which is the ROADMAP "task-windowing" follow-up: the
batch engines' per-tick scatters grow with the total task count, the
streaming tick never does.

Reused from simfast: ``priority_match`` (two-tier cumsum/searchsorted
worker->task matching: understaffed tasks first, then straggler
duplicates), ``churn_and_maintain`` (session churn + TermEst
censoring-corrected latency eviction with the one-sided significance test,
backfilled from the pre-drawn worker banks), ``_init_workers``, and the
counter-based ``_uniform_block`` randomness. Shards advance in lock-step
under ``jax.vmap``; replications vmap once more on top.

Aggregation in the loop is *online* one-coin Dawid-Skene: each vote adds
the voter's estimated log-odds to the task's log-posterior (the E-step
under current accuracy estimates), and every finalized task credits its
voters by agreement with the final label (an incremental hard-EM M-step).
The exact batched full-confusion EM (aggregate.py) is the offline engine
for re-aggregation and QC audits; benchmarks compare the two.

The ``batch_replay`` flag turns the SAME machinery into the naive
fixed-batch baseline — a shard admits work only when its window is
completely drained — so streaming-vs-batch comparisons share every other
code path.

Hybrid learning rides along when ``StreamConfig.learner.enabled``
(:class:`StreamLearnerConfig`): admitted tasks carry feature vectors, the
shared ``repro.learning`` linear learner trains online on finalized
(features, label) pairs, and its log-posterior is fused (product of
experts, ``policy.fuse_posteriors``) into each task's DS posterior —
model-known tasks finalize after ``min_votes_known`` votes and stop
soliciting the crowd, and vote routing drains the most-uncertain window
tasks first. ``refresh_every`` additionally re-runs the exact offline
full-confusion EM (aggregate.py) on the window vote log periodically and
resets the online posteriors and worker-accuracy estimates from it.

Worker-aware routing (``StreamConfig.routing``, routing.py) replaces the
uniform two-tier match with FROG-style scored matching: a worker x slot
score matrix built from the online per-worker accuracy estimates (shared
with the DS vote weights) and a completion-latency EWMA routes
hard/uncertain tasks to accurate workers and easy tasks to fast ones,
greedy-assigned under ``lax.scan`` (``scored_match`` — bit-for-bit
``priority_match`` when the scores are uniform). ``routing.admission =
"uncertain"`` additionally swaps the backlog FIFO for learner-driven
admission: task features are drawn at ARRIVAL and queued tasks enter the
window most-uncertain-first under the current model.
"""
from __future__ import annotations

import collections.abc
import dataclasses
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.crowd import SWITCH_DELAY_S, WAIT_PAY_PER_S, WORK_PAY_PER_RECORD
from repro.embed.config import EmbedConfig
from repro.core.simfast import (
    FastConfig, INF, PopTraced, _aot_timed, _init_workers, _uniform_block,
    churn_and_maintain, draw_latency, priority_match,
)
from repro.obs import timing
from repro.obs.trace import PHASES as TRACE_PHASES
from repro.obs.trace import TraceConfig
from repro.labelstream.arrivals import (
    ArrivalConfig, init_arrival_state, sample_arrivals,
)
from repro.labelstream.policy import (
    PolicyConfig, confidence, fuse_posteriors, learner_known,
    should_finalize, target_outstanding, uncertainty,
)
from repro.labelstream.routing import (
    RoutingConfig, admit_scores, admit_select, learnability_features,
    route_scores, scored_match,
)


@dataclasses.dataclass(frozen=True)
class StreamLearnerConfig:
    """Streaming hybrid learning: the shared ``repro.learning`` linear
    learner rides along with the router (paper §6: the second pillar).

    Admitted tasks carry a feature vector (class-conditional Gaussian,
    ``class_sep`` one-hot means — requires ``n_features >= n_classes``);
    the learner trains online on finalized (features, label) pairs from a
    replay ring buffer and its log-posterior is fused into each task's
    Dawid-Skene posterior (product of experts, weight ramping with the
    training-set size). Tasks the fused posterior already decides finalize
    after ``min_votes_known`` votes and stop soliciting further votes —
    the model labels what it knows, the crowd's votes concentrate on what
    it doesn't. With ``prioritize`` the router also routes votes to the
    most-uncertain window tasks first instead of rotating randomly.
    """
    enabled: bool = False
    n_features: int = 8
    class_sep: float = 1.8
    hard_sep_scale: float = 1.0   # < 1: hard tasks' class separation shrinks
                                  # by this factor — difficulty becomes
                                  # visible in feature space (the signal the
                                  # learnability-aware admission head reads)
    # feature source: "gaussian" draws class-conditional Gaussians in the
    # tick (the historical path, bit-identical); "lm" gathers precomputed
    # LM embeddings of synthetic text tasks from the device-resident
    # repro.embed bank — the SAME uniform draw the Gaussian path would
    # spend on its first feature coordinate picks the bank variant, so the
    # workload randomness (labels, difficulty, votes) is identical
    feature_kind: str = "gaussian"
    embed: Optional[EmbedConfig] = None   # required iff feature_kind="lm"
    prior_scale: float = 1.0      # fusion weight at full ramp
    ramp_n: float = 48.0          # training examples to reach full weight
    known_threshold: float = 0.97 # fused confidence to call a task known
    min_votes_known: int = 1      # crowd votes still required when known
    fit_every: int = 4            # ticks between online Adam updates
    fit_steps: int = 2            # Adam steps per update
    lr: float = 0.05
    l2: float = 1e-3
    buffer: int = 256             # replay buffer of finalized examples
    prioritize: bool = True       # uncertainty-ranked vote routing
    train_crowd_only: bool = True # train only on tasks with >= 1 crowd vote


@dataclasses.dataclass(frozen=True)
class ShardingConfig:
    """Device topology for the streaming tick (engine-native lowering of
    ``repro.scenarios.ShardingSpec``).

    With ``n_devices > 1`` the tick runs under ``shard_map`` over a 1-D
    ``("shard",)`` mesh (``repro.launch.mesh.make_stream_mesh``): each
    device owns ``n_shards / n_devices`` shard groups — ring-buffer window,
    retainer pool and backlog FIFO all live device-resident inside the scan
    carry, and only reduced metrics leave the mesh. Arrival sampling and
    the shared learner are computed replicated from the same keys on every
    device, so any device count produces bit-identical results.

    ``steal="pressure"`` adds cross-shard work stealing each tick: shards
    exchange fixed-shape backlog-depth summaries (all-gather), shards more
    than ``steal_slack`` tasks above the global mean donate up to
    ``steal_max`` of their OLDEST backlog entries, and shards below the
    mean claim them in deterministic shard order (FIFO admission only —
    a backlog entry is an arrival time, so moving it between shards
    preserves task identity and conservation).
    """
    n_devices: int = 1
    steal: str = "none"           # "none" | "pressure"
    steal_max: int = 4            # max tasks a donor shard exports per tick
    steal_slack: int = 2          # backlog excess over global mean to donate


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Static configuration for the streaming service (hashable)."""
    n_shards: int = 2
    pool_size: int = 8            # workers per shard
    window: int = 32              # ring-buffer task slots per shard
    backlog: int = 1024           # backlog FIFO capacity per shard
    n_classes: int = 2
    dt: float = 5.0               # tick length (s)
    max_arrivals_per_tick: int = 64   # per shard; excess is counted dropped
    arrivals: ArrivalConfig = ArrivalConfig()
    policy: PolicyConfig = PolicyConfig()
    batch_replay: bool = False    # naive baseline: drain window, then refill
    # task difficulty mixture: a fraction of tasks where worker accuracy is
    # scaled toward chance (p_correct = 1/C + (acc - 1/C) * difficulty)
    p_hard: float = 0.0
    hard_scale: float = 0.35
    # straggler mitigation + pool maintenance (simfast semantics)
    straggler: bool = True
    max_dup: int = 2
    pm_l: float = float("inf")
    use_termest: bool = True
    min_obs: int = 3
    z: float = 1.0
    alpha: float = 1.0
    # retainer pool / population (simfast defaults)
    recruit_mean_s: float = 45.0
    session_mean_s: float = 1800.0
    median_mu: float = 150.0
    sigma_ln: float = 1.0
    cv_lo: float = 0.3
    cv_hi: float = 1.2
    acc_a: float = 18.0
    acc_b: float = 2.0
    latency_floor: float = 2.0
    # pre-drawn replacement workers per slot. The bank is FINITE: once a
    # slot has churned/evicted through all columns it re-installs its last
    # draw forever, so horizons are effectively bounded by
    # ~bank * session_mean_s per slot (64 * 1800 s = 32 h with defaults) —
    # size it up for longer soaks
    bank: int = 64
    # online worker-accuracy prior (Beta pseudo-counts)
    est_prior_acc: float = 0.85
    est_prior_n: float = 8.0
    # streaming hybrid learner (repro.learning); disabled by default
    learner: StreamLearnerConfig = StreamLearnerConfig()
    # worker-aware task routing (FROG-style scored matching) and backlog
    # admission discipline (FIFO ring vs learner-driven most-uncertain-
    # first); see labelstream/routing.py
    routing: RoutingConfig = RoutingConfig()
    # periodic offline full-confusion Dawid-Skene refresh: every
    # ``refresh_every`` ticks re-run aggregate EM on the window's vote log
    # and reset the online posteriors + worker-accuracy estimates from it
    # (0 = off). The vote log is the per-slot store that also backs
    # finalize-time crediting, so the refresh sees every vote still in the
    # window (finalized tasks have left the system and keep their label).
    refresh_every: int = 0
    refresh_iters: int = 8
    # live serving mode (repro.serving.server): arrivals are INJECTED as
    # per-shard counts instead of sampled, and every backlog/window slot
    # carries a per-shard request uid so finalized labels can be matched
    # back to the submitting HTTP request. The Python-level gate keeps the
    # default (simulator) program bit-identical — no uid buffers exist
    # unless serve=True
    serve: bool = False
    # time-in-system histogram (steady-state percentiles)
    tis_bins: int = 512
    tis_bin_s: float = 4.0
    # device topology: shard groups + cross-shard work stealing
    sharding: ShardingConfig = ShardingConfig()
    # in-loop observability (repro.obs): None compiles the exact historical
    # program; a TraceConfig threads per-phase latency histograms and
    # per-tick activity series through the scan carry. Trace state records
    # only deterministic functions of existing state and consumes no extra
    # uniform blocks, so every shared output key stays bit-identical with
    # tracing on or off (tests/test_obs.py pins both)
    trace: Optional[TraceConfig] = None

    @property
    def fast(self) -> FastConfig:
        """simfast config slice used by the reused pool machinery."""
        return FastConfig(
            pool_size=self.pool_size, retainer=True,
            recruit_mean_s=self.recruit_mean_s,
            session_mean_s=self.session_mean_s,
            median_mu=self.median_mu, sigma_ln=self.sigma_ln,
            cv_lo=self.cv_lo, cv_hi=self.cv_hi,
            acc_a=self.acc_a, acc_b=self.acc_b,
            pm_l=self.pm_l, use_termest=self.use_termest,
            min_obs=self.min_obs, z=self.z, alpha=self.alpha,
            latency_floor=self.latency_floor, bank=self.bank,
        )


def heterogeneous_stream_config(**overrides) -> StreamConfig:
    """The canonical heterogeneous-pool workload where worker-aware routing
    has signal to exploit: wide Beta(2, 1) worker-accuracy spread, a weak
    estimation prior so the online estimates actually separate workers,
    hour-long sessions so they stay valid, and drip adaptive redundancy
    (one outstanding vote, finalize at 0.95). Shared by bench_labelstream
    section 5 (the regression-gated measurement behind the committed
    baseline), the routing tests, and the demo so the three cannot
    silently measure different workloads. ``overrides`` are StreamConfig
    fields applied on top."""
    base = dict(
        n_shards=2, pool_size=8, window=16, dt=5.0, tis_bin_s=8.0,
        arrivals=ArrivalConfig(kind="poisson", rate=0.012),
        acc_a=2.0, acc_b=1.0, est_prior_n=2.0, session_mean_s=3600.0,
        policy=PolicyConfig(adaptive=True, votes_cap=5, conf_threshold=0.95,
                            min_votes=1, max_outstanding=1))
    base.update(overrides)
    return StreamConfig(**base)


class StreamTraced(NamedTuple):
    """Traced ABSOLUTE overrides on the static stream knobs — the stream
    engine's multi-axis sweep bundle (``repro.grid`` backend).

    Like :class:`repro.core.simfast.PopTraced`, each leaf replaces the
    same-named static value with a traced absolute; ``0``/``0.0`` is the
    "not overridden" sentinel. ``rate`` replaces ``arrivals.rate`` (the
    poisson rate / mmpp calm rate / diurnal mean — exact override, unlike
    the multiplicative ``rate_scale``, which also scales the mmpp burst
    rate). ``votes_cap`` is the masked effective cap of
    ``run_stream_votes_sweep`` (buffers stay sized at the static cap);
    the Beta accuracy params reach the worker-bank init via the
    reparameterized draw. A bundle whose values equal the static config
    reproduces ``run_stream`` bit for bit.

    ``p_hard``/``hard_scale`` override the task-difficulty mixture; their
    valid range includes 0.0, so their "not overridden" sentinel is any
    NEGATIVE value (-1.0 by default), not 0.
    """
    rate: jnp.ndarray = 0.0
    votes_cap: jnp.ndarray = 0
    acc_a: jnp.ndarray = 0.0
    acc_b: jnp.ndarray = 0.0
    p_hard: jnp.ndarray = -1.0
    hard_scale: jnp.ndarray = -1.0


# --------------------------------------------------------------------------
# state init
# --------------------------------------------------------------------------

def _init_window(cfg: StreamConfig):
    Ws, C, cap = cfg.window, cfg.n_classes, cfg.policy.votes_cap
    win = dict(
        active=jnp.zeros((Ws,), bool),
        arrival_t=jnp.zeros((Ws,)),
        difficulty=jnp.ones((Ws,)),
        true_label=jnp.zeros((Ws,), jnp.int32),
        n_votes=jnp.zeros((Ws,), jnp.int32),
        logpost=jnp.zeros((Ws, C)),
        # per-slot vote store (worker slot + label) for finalize-time credit
        vote_wid=jnp.zeros((Ws + 1, cap), jnp.int32),
        vote_lab=jnp.zeros((Ws + 1, cap), jnp.int32),
    )
    if cfg.learner.enabled:
        win["feat"] = jnp.zeros((Ws, cfg.learner.n_features))
    if cfg.serve:
        # per-slot request uid (serve mode): -1 marks "no request here"
        win["uid"] = jnp.full((Ws,), -1, jnp.int32)
    if cfg.trace is not None and cfg.trace.phases:
        # per-slot phase accounting for the latency-source decomposition:
        # admission instant, accumulated staffed ("work") vs unstaffed
        # ("wait") tick time, and the instant of the last posterior
        # evidence (admission or credited vote) for the finalize lag
        win["admit_t"] = jnp.zeros((Ws,))
        win["work_s"] = jnp.zeros((Ws,))
        win["wait_s"] = jnp.zeros((Ws,))
        win["last_evt_t"] = jnp.zeros((Ws,))
    return win


def _init_shard(cfg: StreamConfig, key, pop=None):
    ws, banks = _init_workers(cfg.fast, key, pop)
    P, Q = cfg.pool_size, cfg.backlog
    ws["est_correct"] = jnp.zeros((P,))
    ws["est_n"] = jnp.zeros((P,))
    # per-worker completion-latency EWMA (the routing speed axis); starts
    # at the population median so an unobserved worker scores neutral
    ws["lat_ewma"] = jnp.full((P,), cfg.median_mu)
    if cfg.routing.admission != "fifo":
        # slot-array backlog: task identity (features, difficulty, label)
        # is drawn at ARRIVAL and stored so admission can rank by model
        # uncertainty; row Q is the dump row for masked scatters/gathers
        bl = dict(times=jnp.zeros((Q + 1,)),
                  diff=jnp.ones((Q + 1,)),
                  tlab=jnp.zeros((Q + 1,), jnp.int32),
                  feat=jnp.zeros((Q + 1, cfg.learner.n_features)),
                  occ=jnp.zeros((Q,), bool),
                  count=jnp.zeros((), jnp.int32))
    else:
        # FIFO ring of arrival times. Serve + lm binds task identity at
        # ARRIVAL (an injected request's label/embedding must ride the
        # ring to its admission tick), so the ring carries it alongside the
        # times
        bl = dict(times=jnp.zeros((Q + 1,)),
                  head=jnp.zeros((), jnp.int32),
                  count=jnp.zeros((), jnp.int32))
        if cfg.serve and cfg.learner.feature_kind == "lm":
            bl["tlab"] = jnp.zeros((Q + 1,), jnp.int32)
            bl["diff"] = jnp.ones((Q + 1,))
            bl["feat"] = jnp.zeros((Q + 1, cfg.learner.n_features))
    if cfg.serve:
        # per-entry request uid (serve mode): -1 marks "no request here"
        bl["uid"] = jnp.full((Q + 1,), -1, jnp.int32)
    return ws, banks, _init_window(cfg), bl


# --------------------------------------------------------------------------
# one shard, one tick
# --------------------------------------------------------------------------

def _acc_hat(cfg: StreamConfig, ws):
    """Beta-smoothed clipped online worker-accuracy estimate — the SAME
    quantity that weights online Dawid-Skene votes and feeds the routing
    accuracy axis (the shared-counters invariant the README documents)."""
    return jnp.clip(
        (cfg.est_prior_acc * cfg.est_prior_n + ws["est_correct"])
        / (cfg.est_prior_n + ws["est_n"]), 0.52, 0.995)


def _task_features(u1, u2, tl, diff, L: StreamLearnerConfig, C: int):
    """Class-conditional Gaussian features (one-hot class means scaled by
    ``class_sep``, unit Box-Muller noise) for tasks with true labels
    ``tl`` — the observable side the learner generalizes over. Shared by
    the admission-time (FIFO) and arrival-time (uncertain admission)
    draws so the two backlog disciplines sample the same feature
    distribution. With ``hard_sep_scale < 1`` hard tasks (``diff < 1``)
    get their class separation shrunk by that factor, so difficulty is
    observable from features (the Python-level gate keeps the default
    path bit-identical to the historical draw)."""
    nrm = jnp.sqrt(-2.0 * jnp.log1p(-u1)) * jnp.cos(2.0 * jnp.pi * u2)
    means = L.class_sep * jnp.eye(C, L.n_features)
    base = means[tl]
    if L.hard_sep_scale != 1.0:
        base = base * jnp.where(diff < 1.0, L.hard_sep_scale, 1.0)[..., None]
    return base + nrm


def _arrival_identity(cfg: StreamConfig, ua, ph, hs, bank, labels_in,
                      feat_in):
    """Difficulty, true label and features of a tick's arrivals from the
    uniform block ``ua``. With lm features ``ua[2]`` (the Gaussian draw's
    first feature coordinate) picks the bank variant, so the other streams
    stay bit-identical across kinds; injected labels (>= 0) and real-text
    embeddings (finite ``feat_in`` rows) override the draws."""
    L, C, F = cfg.learner, cfg.n_classes, cfg.learner.n_features
    diff_a = jnp.where(ua[0] < ph, hs, 1.0)
    tl_a = jnp.floor(ua[1] * C).astype(jnp.int32).clip(0, C - 1)
    if L.feature_kind != "lm":
        return diff_a, tl_a, _task_features(
            ua[2:2 + F].T, ua[2 + F:2 + 2 * F].T, tl_a, diff_a, L, C)
    from repro.embed.bank import bank_gather
    if labels_in is not None:
        tl_a = jnp.where(labels_in >= 0, labels_in, tl_a)
    feat_a = bank_gather(bank, ua[2], tl_a, diff_a)
    if feat_in is not None:
        feat_a = jnp.where(jnp.isfinite(feat_in[:, 0])[:, None], feat_in,
                           feat_a)
    return diff_a, tl_a, feat_a


def refresh_due(cfg: StreamConfig, step):
    """Whether tick ``step`` (0-based, may be traced) runs the periodic
    full-confusion refresh: the last of every ``refresh_every`` ticks."""
    k = cfg.refresh_every
    return k > 0 and step % k == k - 1


def _shard_tick(cfg: StreamConfig, ws, banks, win, bl, n_arr, t, step, seed,
                warmup_t, lW, lb, fuse_w, gW, gb, cap_eff=None,
                p_hard_t=None, hard_scale_t=None, uid_base=None,
                bank=None, feat_in=None, labels_in=None):
    P, Ws, C = cfg.pool_size, cfg.window, cfg.n_classes
    Q, M, cap = cfg.backlog, cfg.max_arrivals_per_tick, cfg.policy.votes_cap
    # cap_eff is the (possibly traced) EFFECTIVE vote budget for the masked
    # votes-cap sweep: buffers stay sized at the static cap (= the sweep
    # max), the effective cap gates vote admission / finalization /
    # outstanding targets, and columns past it are never touched or read
    cap_t = cap if cap_eff is None else cap_eff
    # traced difficulty-mixture overrides (grid/sweep axes); None keeps the
    # static Python-float draw, bit-identical to the historical program
    ph = cfg.p_hard if p_hard_t is None else p_hard_t
    hs = cfg.hard_scale if hard_scale_t is None else hard_scale_t
    pol, fast, L, R = cfg.policy, cfg.fast, cfg.learner, cfg.routing
    up = _uniform_block(seed, step, 8 * P).reshape(8, P)

    # ---- backlog push + admission into free window slots -----------------
    with jax.named_scope("admission"):
        free = ~win["active"]
        if cfg.batch_replay:
            # naive fixed-batch replay: refill only once the window is drained
            gate = free.all()
        else:
            gate = jnp.ones((), bool)
        frank = (jnp.cumsum(free) - 1).astype(jnp.int32)
        featw = None
        if R.admission != "fifo":
            # learner-driven admission: task identity (difficulty, true label,
            # features) is drawn at ARRIVAL and stored in the slot-array
            # backlog; admission ranks queued tasks by the current model's
            # uncertainty on their features and takes the most uncertain first
            # (an untrained model ties everything and slot order wins);
            # "uncertain_learnable" weights uncertainty by the learnability
            # head's estimate so chance-level-hard tasks stop hogging slots
            F = L.n_features
            occ = bl["occ"]
            space = Q - occ.sum()
            n_push = jnp.minimum(n_arr, space)
            dropped = (n_arr - n_push).astype(jnp.int32)
            slot = jnp.arange(M, dtype=jnp.int32)
            # i-th arrival -> i-th free backlog slot (searchsorted rank trick)
            csum = jnp.cumsum((~occ).astype(jnp.int32))
            dst = jnp.searchsorted(csum, slot + 1).astype(jnp.int32)
            ok = slot < n_push
            dstw = jnp.where(ok, dst, Q)          # row Q is the dump row
            ua = _uniform_block(seed ^ jnp.uint32(0x0BAD5EED), step,
                                (2 + 2 * F) * M).reshape(2 + 2 * F, M)
            diff_a, tl_a, feat_a = _arrival_identity(cfg, ua, ph, hs, bank,
                                                     labels_in, feat_in)
            bl_times = bl["times"].at[dstw].set(t)
            bl_diff = bl["diff"].at[dstw].set(diff_a)
            bl_tlab = bl["tlab"].at[dstw].set(tl_a)
            bl_feat = bl["feat"].at[dstw].set(feat_a)
            if cfg.serve:
                bl_uid = bl["uid"].at[dstw].set(uid_base + slot)
            occ = jnp.concatenate([occ, jnp.zeros((1,), bool)]
                                  ).at[dstw].set(True)[:Q]
            n_adm = jnp.where(gate, jnp.minimum(occ.sum(), free.sum()), 0
                              ).astype(jnp.int32)
            u_bl = uncertainty(bl_feat[:Q] @ lW + lb)
            if R.admission == "uncertain_learnable":
                adm_key = admit_scores(u_bl, bl_feat[:Q], gW, gb)
            else:
                adm_key = u_bl
            admit_bl, order = admit_select(adm_key, occ, n_adm)
            admit = free & (frank < n_adm)
            # r-th free window slot takes the r-th most-uncertain queued task
            src = jnp.where(admit, order[frank.clip(0, Q - 1)], Q)
            arr_t = bl_times[src]
            diff = bl_diff[src]
            tl = bl_tlab[src]
            featw = bl_feat[src]
            occ = occ & ~admit_bl
            bl = dict(times=bl_times, diff=bl_diff, tlab=bl_tlab, feat=bl_feat,
                      occ=occ, count=occ.sum().astype(jnp.int32))
            if cfg.serve:
                uid_w = bl_uid[src]
                bl["uid"] = bl_uid
            bl_count = bl["count"]
        else:
            # FIFO ring: each per-entry ring ``_init_shard`` gave the backlog
            # (times; uid in serve mode; tlab/diff/feat in serve + lm) takes
            # the arrivals at its tail and gives up the admitted at its head
            space = Q - bl["count"]
            n_push = jnp.minimum(n_arr, space)
            dropped = (n_arr - n_push).astype(jnp.int32)
            slot = jnp.arange(M, dtype=jnp.int32)
            pos = (bl["head"] + bl["count"] + slot) % Q
            posw = jnp.where(slot < n_push, pos, Q)

            def arriving():
                # (ring, this tick's arrivals' values) for each ring held
                yield "times", t
                if "uid" in bl:
                    yield "uid", uid_base + slot
                if "tlab" in bl:
                    ua = _uniform_block(seed ^ jnp.uint32(0x0BAD5EED), step,
                                        3 * M).reshape(3, M)
                    diff_a, tl_a, feat_a = _arrival_identity(
                        cfg, ua, ph, hs, bank, labels_in, feat_in)
                    yield from (("tlab", tl_a), ("diff", diff_a),
                                ("feat", feat_a))

            bl = dict(bl)
            for k, v in arriving():
                bl[k] = bl[k].at[posw].set(v)
            bl["count"] = bl["count"] + n_push
            n_adm = jnp.where(gate, jnp.minimum(bl["count"], free.sum()), 0
                              ).astype(jnp.int32)
            admit = free & (frank < n_adm)
            src = jnp.where(admit, (bl["head"] + frank) % Q, Q)
            arr_t = bl["times"][src]
            uid_w = bl["uid"][src] if "uid" in bl else None
            bl["head"] = (bl["head"] + n_adm) % Q
            bl["count"] = bl["count"] - n_adm
            bl_count = bl["count"]
            if "tlab" in bl:
                # the identity rode the ring from arrival
                diff, tl = bl["diff"][src], bl["tlab"][src]
                featw = bl["feat"][src]
            else:
                # fresh-task draws at ADMISSION (difficulty mixture + label)
                uw = _uniform_block(seed ^ jnp.uint32(0x33CC33CC), step, 2 * Ws
                                    ).reshape(2, Ws)
                diff = jnp.where(uw[0] < ph, hs, 1.0)
                tl = jnp.floor(uw[1] * C).astype(jnp.int32).clip(0, C - 1)
                if L.enabled:
                    F = L.n_features
                    uf = _uniform_block(seed ^ jnp.uint32(0x5EEDF00D), step,
                                        2 * Ws * F).reshape(2, Ws, F)
                    if L.feature_kind == "lm":
                        # same-shaped block as the Gaussian draw; its first
                        # column picks the bank variant, the rest is unread
                        from repro.embed.bank import bank_gather
                        featw = bank_gather(bank, uf[0, :, 0], tl, diff)
                    else:
                        featw = _task_features(uf[0], uf[1], tl, diff, L, C)
        win = dict(win)
        win["active"] = win["active"] | admit
        win["arrival_t"] = jnp.where(admit, arr_t, win["arrival_t"])
        win["difficulty"] = jnp.where(admit, diff, win["difficulty"])
        win["true_label"] = jnp.where(admit, tl, win["true_label"])
        win["n_votes"] = jnp.where(admit, 0, win["n_votes"])
        win["logpost"] = jnp.where(admit[:, None], 0.0, win["logpost"])
        if L.enabled:
            win["feat"] = jnp.where(admit[:, None], featw, win["feat"])
        if cfg.serve:
            win["uid"] = jnp.where(admit, uid_w, win["uid"])
        tr = cfg.trace
        tr_ph = tr is not None and tr.phases
        if tr_ph:
            win["admit_t"] = jnp.where(admit, t, win["admit_t"])
            win["work_s"] = jnp.where(admit, 0.0, win["work_s"])
            win["wait_s"] = jnp.where(admit, 0.0, win["wait_s"])
            win["last_evt_t"] = jnp.where(admit, t, win["last_evt_t"])

    # ---- completions -> votes -> online posterior -----------------------
    with jax.named_scope("votes"):
        ws = dict(ws)
        active_w = ws["assigned"] >= 0
        comp = active_w & (ws["busy_until"] <= t)
        a_idx = jnp.maximum(ws["assigned"], 0)
        tid = jnp.where(comp, ws["assigned"], Ws)
        lat = jnp.where(comp, ws["busy_until"] - ws["start_t"], 0.0)
        d_w = win["difficulty"][a_idx]
        p_corr = jnp.clip(1.0 / C + (ws["acc"] - 1.0 / C) * d_w, 1.0 / C,
                          0.995)
        tl_w = win["true_label"][a_idx]
        correct = up[0] < p_corr
        wrong = jnp.floor(up[1] * max(C - 1, 1)).astype(jnp.int32)
        label = jnp.where(correct, tl_w,
                          jnp.where(wrong >= tl_w, wrong + 1, wrong))
        # vote slot position: n_votes before this tick + rank among this tick's
        # completions of the same task; votes landing past the cap are dropped
        # (paid straggler duplicates that lost the race to the budget)
        pr = jnp.arange(P)
        prior_ct = ((tid[None, :] == tid[:, None]) & comp[None, :]
                    & (pr[None, :] < pr[:, None])).sum(-1).astype(jnp.int32)
        vpos = win["n_votes"][a_idx] + prior_ct
        keep = comp & (vpos < cap_t)
        tid_k = jnp.where(keep, tid, Ws)
        vpos_k = jnp.where(keep, vpos, 0).clip(0, cap - 1)
        win["vote_wid"] = win["vote_wid"].at[tid_k, vpos_k].set(
            jnp.where(keep, pr, win["vote_wid"][tid_k, vpos_k]))
        win["vote_lab"] = win["vote_lab"].at[tid_k, vpos_k].set(
            jnp.where(keep, label, win["vote_lab"][tid_k, vpos_k]))
        # online DS E-step: add the voter's estimated log-odds to the voted
        # class
        a_e = _acc_hat(cfg, ws)
        delta = jnp.log(a_e * max(C - 1, 1) / (1.0 - a_e))
        win["logpost"] = (jnp.concatenate(
            [win["logpost"], jnp.zeros((1, C))])
            .at[tid_k, label].add(jnp.where(keep, delta, 0.0)))[:Ws]
        win["n_votes"] = (jnp.concatenate(
            [win["n_votes"], jnp.zeros((1,), jnp.int32)])
            .at[tid_k].add(keep.astype(jnp.int32)))[:Ws]
        if tr_ph:
            # completion instant of this tick's credited votes (busy_until
            # still holds it here; the slot is reset to INF only after the
            # worker-bookkeeping block below) — the finalize lag measures
            # from the LAST evidence the posterior saw
            win["last_evt_t"] = (jnp.concatenate(
                [win["last_evt_t"], jnp.zeros((1,))])
                .at[tid_k].max(jnp.where(keep, ws["busy_until"], -INF)))[:Ws]

    # ---- periodic offline full-confusion Dawid-Skene refresh ------------
    # every refresh_every ticks, re-run the exact batched EM (aggregate.py)
    # on the window's vote log and reset the online posteriors and worker-
    # accuracy estimates from it — the online one-coin increments drift
    # (stale accuracy estimates at vote time are never revisited); the
    # offline EM re-explains every stored vote under the final confusions
    with jax.named_scope("refresh"):
        if cfg.refresh_every > 0:
            from repro.labelstream.aggregate import _ds_em, estep_mode

            use_kernel, interpret = estep_mode()

            def _refresh(_):
                vmask_r = (jnp.arange(cap)[None, :]
                           < win["n_votes"][:, None]) \
                    & win["active"][:, None]
                em = _ds_em(win["vote_lab"][:Ws], win["vote_wid"][:Ws],
                            vmask_r, P + 1, C, cfg.refresh_iters, False,
                            use_kernel, interpret)
                lp = jnp.where((win["active"] & (win["n_votes"] > 0))[:, None],
                               em["log_posterior"], win["logpost"])
                vpw = em["votes_per_worker"][:P]
                return lp, em["accuracy"][:P] * vpw, vpw

            win["logpost"], ws["est_correct"], ws["est_n"] = jax.lax.cond(
                refresh_due(cfg, step), _refresh,
                lambda _: (win["logpost"], ws["est_correct"], ws["est_n"]),
                None)

    # ---- learner fusion (product of experts) ----------------------------
    # the adaptive-redundancy policy consumes the learner posterior fused
    # with the DS posterior: tasks the model already knows finalize after
    # min_votes_known crowd votes and stop soliciting further votes
    with jax.named_scope("fusion"):
        if L.enabled:
            model_lp = jax.nn.log_softmax(win["feat"] @ lW + lb, axis=-1)
            fused = fuse_posteriors(win["logpost"], model_lp, fuse_w)
            known, known_fin = learner_known(
                fused, win["n_votes"], threshold=L.known_threshold,
                min_votes_known=L.min_votes_known)
        else:
            fused = win["logpost"]
            known = jnp.zeros((Ws,), bool)
            known_fin = known

    # ---- finalization (adaptive redundancy) -----------------------------
    with jax.named_scope("finalize"):
        fin, conf = should_finalize(fused, win["n_votes"], pol, cap=cap_eff)
        fin = (fin | known_fin) & win["active"]
        result = fused.argmax(-1)
        tis = jnp.where(fin, t - win["arrival_t"], 0.0)
        # steady-state metrics count tasks by ARRIVAL-time warmth (matching the
        # offered-rate gate), so warmup queueing cannot leak into the histogram
        # and sustained throughput is measured against the same task population
        wfin = fin & (win["arrival_t"] >= warmup_t)
        nbin = cfg.tis_bins
        hbin = jnp.clip((tis / cfg.tis_bin_s).astype(jnp.int32), 0, nbin - 1)
        hist_d = jnp.zeros((nbin + 1,), jnp.int32).at[
            jnp.where(wfin, hbin, nbin)].add(1)[:nbin]
        done_d = wfin.sum()
        corr_d = (wfin & (result == win["true_label"])).sum()
        tis_d = (tis * wfin).sum()
        votesfin_d = (win["n_votes"] * wfin).sum()
        if tr_ph:
            # latency-source decomposition at finalize time (paper §2's
            # taxonomy, Table-1-style): backlog_wait + window_wait + work_time
            # == time-in-system exactly (tick accounting below), finalize_lag
            # is the overlapping tail past the last posterior evidence
            ph_vals = dict(
                backlog_wait=win["admit_t"] - win["arrival_t"],
                window_wait=win["wait_s"],
                work_time=win["work_s"],
                finalize_lag=jnp.clip(t - win["last_evt_t"], 0.0, None),
            )
            ph_hist = {}
            ph_sum = {}
            for pk in TRACE_PHASES:
                pb = jnp.clip((ph_vals[pk] / cfg.tis_bin_s).astype(jnp.int32),
                              0, nbin - 1)
                ph_hist[pk] = jnp.zeros((nbin + 1,), jnp.int32).at[
                    jnp.where(wfin, pb, nbin)].add(1)[:nbin]
                ph_sum[pk] = (ph_vals[pk] * wfin).sum()
    # credit voters of finalized tasks by agreement with the final label
    # (incremental hard-EM M-step for the online accuracy estimates)
    with jax.named_scope("credit"):
        vmask = (jnp.arange(cap)[None, :] < win["n_votes"][:Ws, None]) \
            & fin[:, None]
        vw = jnp.where(vmask, win["vote_wid"][:Ws], P)
        agree = (win["vote_lab"][:Ws] == result[:, None]) & vmask
        ws["est_correct"] = ws["est_correct"] + jnp.zeros((P + 1,)).at[
            vw.reshape(-1)].add(agree.reshape(-1).astype(jnp.float32))[:P]
        ws["est_n"] = ws["est_n"] + jnp.zeros((P + 1,)).at[
            vw.reshape(-1)].add(vmask.reshape(-1).astype(jnp.float32))[:P]
        win["active"] = win["active"] & ~fin

    # ---- worker bookkeeping: completers + straggler losers --------------
    with jax.named_scope("bookkeeping"):
        lose = active_w & ~comp & fin[a_idx]
        win_lat = jnp.zeros((Ws + 1,)).at[tid].max(lat)[:Ws]
        winner = jnp.where(lose, win_lat[a_idx], 0.0)
        freed = comp | lose
        ws["n_completed"] = ws["n_completed"] + comp
        ws["n_terminated"] = ws["n_terminated"] + lose
        ws["comp_sum"] = ws["comp_sum"] + lat * comp
        ws["comp_sqsum"] = ws["comp_sqsum"] + lat * lat * comp
        ws["term_sum"] = ws["term_sum"] + winner * lose
        # completion-latency EWMA: the routing speed axis (route_scores)
        ws["lat_ewma"] = jnp.where(
            comp, (1.0 - R.ewma_alpha) * ws["lat_ewma"] + R.ewma_alpha * lat,
            ws["lat_ewma"])
        ws["cost_work"] = ws["cost_work"] + freed.sum() * WORK_PAY_PER_RECORD
        ws["blocked_until"] = jnp.where(
            comp, ws["busy_until"],
            jnp.where(lose, t + SWITCH_DELAY_S, ws["blocked_until"]))
        ws["assigned"] = jnp.where(freed, -1, ws["assigned"])
        ws["busy_until"] = jnp.where(freed, INF, ws["busy_until"])

    # ---- churn + latency maintenance (shared simfast machinery) ---------
    with jax.named_scope("maintenance"):
        ws, leave = churn_and_maintain(fast, ws, banks, t, up[2], up[3],
                                       cfg.recruit_mean_s)
        ws["est_correct"] = jnp.where(leave, 0.0, ws["est_correct"])
        ws["est_n"] = jnp.where(leave, 0.0, ws["est_n"])
        ws["lat_ewma"] = jnp.where(leave, cfg.median_mu, ws["lat_ewma"])
        # stored votes key on the pool slot: remap votes cast by departing
        # workers to the dump slot P so finalize-time crediting cannot charge
        # the replacement worker for its predecessor's answers
        leave_pad = jnp.concatenate([leave, jnp.zeros((1,), bool)])
        win["vote_wid"] = jnp.where(leave_pad[win["vote_wid"]], P,
                                    win["vote_wid"])

    # ---- assignment: understaffed tasks first, then duplicates ----------
    with jax.named_scope("assign"):
        avail = (ws["assigned"] < 0) & (ws["blocked_until"] <= t) \
            & (ws["session_end"] > t)
        n_asg = jnp.zeros((Ws + 1,), jnp.int32).at[
            jnp.where(ws["assigned"] >= 0, ws["assigned"], Ws)].add(1)[:Ws]
        want = target_outstanding(win["n_votes"], pol, cap=cap_eff)
        if L.enabled:
            # a model-known task requests only the crowd votes it still needs
            # to clear the min_votes_known floor — the learner posterior
            # covers the rest, so the saved votes concentrate on unknown
            # tasks
            want = jnp.where(known, jnp.minimum(
                want, jnp.maximum(L.min_votes_known - win["n_votes"], 0)),
                want)
        tier1 = win["active"] & (n_asg < want)
        if cfg.straggler:
            extra = jnp.minimum(want, cfg.max_dup)
            tier2 = win["active"] & (want > 0) & (n_asg >= want) \
                & (n_asg < want + extra)
        else:
            tier2 = jnp.zeros((Ws,), bool)
        if R.enabled:
            # FROG-style worker-aware routing: score workers x window slots
            # from the ONLINE per-worker accuracy estimate (the same counters
            # behind the DS vote weights, refreshed after this tick's
            # crediting and churn) and the completion-latency EWMA, then
            # greedy-match under scan. Task uncertainty comes from the FUSED
            # posterior, so an enabled learner sharpens the routing for free;
            # with w_acc == w_speed == 0 this is exactly priority_match
            shift = (_uniform_block(seed ^ jnp.uint32(0xA5A5A5A5), step, 1)[0]
                     * Ws).astype(jnp.int32)
            scores = route_scores(_acc_hat(cfg, ws), ws["lat_ewma"],
                                  uncertainty(fused), R)
            take, task_for_w, _, _ = scored_match(scores, avail, tier1, tier2,
                                                  shift)
        elif L.enabled and L.prioritize:
            # learner-driven prioritization: route votes to the window tasks
            # with the LOWEST fused confidence first (priority_match drains
            # eligible tasks in slot order, so matching in permuted slot space
            # and mapping back yields most-uncertain-first routing)
            unc = jnp.where(win["active"], -confidence(fused), -jnp.inf)
            perm = jnp.argsort(-unc, stable=True).astype(jnp.int32)
            take, task_p, _, _ = priority_match(
                avail, tier1[perm], tier2[perm], jnp.zeros((), jnp.int32))
            task_for_w = perm[task_p]
        else:
            shift = (_uniform_block(seed ^ jnp.uint32(0xA5A5A5A5), step, 1)[0]
                     * Ws).astype(jnp.int32)
            take, task_for_w, _, _ = priority_match(avail, tier1, tier2, shift)
        lat_new = draw_latency(fast, ws["mu"], ws["sigma"], up[6], up[7])
        ws["assigned"] = jnp.where(take, task_for_w, ws["assigned"])
        ws["busy_until"] = jnp.where(take, t + lat_new, ws["busy_until"])
        ws["start_t"] = jnp.where(take, t, ws["start_t"])
        ws["n_started"] = ws["n_started"] + take
        waiting = avail & ~take
        ws["cost_wait"] = ws["cost_wait"] \
            + waiting.sum() * cfg.dt * WAIT_PAY_PER_S

        if tr_ph:
            # attribute this tick to work vs wait for every still-active task:
            # staffed (>= 1 assigned worker after this tick's matching) ticks
            # count as work time, active-but-unstaffed ticks as window wait.
            # A task admitted at tick k and finalized at tick k+m accumulates
            # exactly m ticks here (its finalize tick doesn't count: the slot
            # already left "active" above), so backlog_wait + window_wait +
            # work_time == time-in-system exactly
            n_asg_post = jnp.zeros((Ws + 1,), jnp.int32).at[
                jnp.where(ws["assigned"] >= 0, ws["assigned"], Ws)].add(1)[:Ws]
            staffed = win["active"] & (n_asg_post > 0)
            win["work_s"] = win["work_s"] + jnp.where(staffed, cfg.dt, 0.0)
            win["wait_s"] = win["wait_s"] + jnp.where(
                win["active"] & ~staffed, cfg.dt, 0.0)

    metrics = dict(hist=hist_d, done=done_d, correct=corr_d, sum_tis=tis_d,
                   votes_fin=votesfin_d,
                   completions=(comp & (win["arrival_t"][a_idx]
                                        >= warmup_t)).sum(),
                   done_all=fin.sum(), dropped=dropped,
                   backlog=bl_count, in_flight=win["active"].sum(),
                   model_known=(wfin & known).sum())
    if cfg.serve:
        # per-slot finalization outputs for the live serving front end:
        # which slots finalized this tick, their request uids, fused-label
        # answers and posterior confidence — with the per-shard counts, the
        # ONLY data that leaves the device each tick, packed into one buffer
        # by the serve tick (the router state itself stays resident)
        metrics["srv_fin"] = fin
        metrics["srv_uid"] = win["uid"]
        metrics["srv_label"] = result.astype(jnp.int32)
        metrics["srv_votes"] = win["n_votes"]
        metrics["srv_conf"] = conf
        metrics["srv_tis"] = tis
    if tr_ph:
        for pk in TRACE_PHASES:
            metrics["ph_" + pk] = ph_hist[pk]
            metrics["ps_" + pk] = ph_sum[pk]
    if tr is not None and tr.per_tick:
        metrics["votes"] = keep.sum()
        metrics["busy_workers"] = (ws["assigned"] >= 0).sum()
        metrics["idle_workers"] = waiting.sum()
        if R.admission != "fifo":
            # mean admission score over the queued backlog (routing
            # quality: how uncertain is what we are still admitting)
            metrics["adm_score"] = (jnp.where(admit_bl, adm_key, 0.0).sum()
                                    / jnp.maximum(admit_bl.sum(), 1))
    if L.enabled:
        # finalized (features, label) pairs feed the replay buffer the
        # driver trains on. Training labels come from the CROWD-ONLY
        # posterior (not the fused result): a confident-but-wrong model
        # that finalizes over a disagreeing vote must not feed its own
        # prediction back into its training set (self-training feedback
        # loop); with train_crowd_only the pair additionally requires at
        # least one crowd vote so zero-vote model finalizations never
        # train the model on itself
        tmask = fin & (win["n_votes"] >= 1) if L.train_crowd_only else fin
        train = dict(mask=tmask, feat=win["feat"],
                     label=win["logpost"].argmax(-1))
        if R.admission == "uncertain_learnable":
            # learnability target: did the MODEL's prediction agree with
            # the CROWD's final label? On learnable tasks both converge
            # to the truth (agreement ~ model accuracy, high); on
            # chance-level tasks the crowd label is a coin flip, so
            # agreement sits at chance no matter how confident either
            # party looks. This is the one finalize-time observable with
            # a clean statistical gap: posterior confidence, vote counts
            # and model-known status all fail here, because random votes
            # frequently produce confident-looking 2-0/4-1 posteriors
            # and a sharply-trained linear model is confidently WRONG on
            # small-norm noise features. Cold start is graceful: an
            # untrained model agrees at chance everywhere, the head
            # learns ~constant, and the admission ranking degrades to
            # plain ``uncertain``.
            model_pred = (win["feat"] @ lW + lb).argmax(-1)
            train["learnable"] = (model_pred
                                  == win["logpost"].argmax(-1)
                                  ).astype(jnp.int32)
    else:
        train = dict(mask=jnp.zeros((Ws,), bool))
    return ws, win, bl, metrics, train


# --------------------------------------------------------------------------
# cross-shard work stealing
# --------------------------------------------------------------------------

def _steal_plan(counts, steal_max: int, slack: int):
    """Deterministic fixed-shape rebalance plan from global backlog depths.

    ``counts`` is the (S,)-shaped all-gathered backlog-pressure summary.
    Shards more than ``slack`` above the global mean donate up to
    ``steal_max`` tasks, shards below the mean claim up to ``steal_max``;
    the matched volume ``min(sum(give), sum(take))`` is filled greedily in
    shard order on both sides, so every device computes the identical plan
    from the identical summary (donor and receiver sets are disjoint:
    donors sit strictly above the mean, receivers strictly below)."""
    S = counts.shape[0]
    target = counts.sum() // S
    give0 = jnp.clip(counts - target - slack, 0, steal_max)
    take0 = jnp.clip(target - counts, 0, steal_max)
    vol = jnp.minimum(give0.sum(), take0.sum())
    give = jnp.clip(vol - (jnp.cumsum(give0) - give0), 0, give0)
    take = jnp.clip(vol - (jnp.cumsum(take0) - take0), 0, take0)
    return give, take


def _steal_rebalance(cfg: StreamConfig, bl, mesh: _Mesh):
    """Move backlog work from hot shards to starved ones (FIFO layout).

    Donors pop their OLDEST entries (head side, preserving arrival times =
    task identity under FIFO admission), the donations are all-gathered as
    a fixed (S, steal_max) block keyed by deterministic donation rank, and
    receivers append their claimed ranks at the tail. Pure data movement:
    the global backlog multiset is unchanged (conservation), and the plan
    is a function of the gathered depth summary only (determinism).
    Returns (bl, received, donated) with (S_local,) per-shard counts."""
    sh = cfg.sharding
    S, Q, K = cfg.n_shards, cfg.backlog, sh.steal_max
    Sl, lo, _gat = mesh.Sl, mesh.lo, mesh.gather
    counts = _gat(bl["count"])                              # (S,)
    give, take = _steal_plan(counts, K, sh.steal_slack)
    gcum = jnp.cumsum(give) - give                          # donation ranks
    tcum = jnp.cumsum(take) - take                          # claim ranks
    sl = lambda x: jax.lax.dynamic_slice_in_dim(x, lo, Sl)
    give_l, take_l, tcum_l = sl(give), sl(take), sl(tcum)
    k = jnp.arange(K)
    # donors pop their oldest entries off the ring head
    pos = (bl["head"][:, None] + k[None, :]) % Q            # (Sl, K)
    don_l = jnp.take_along_axis(bl["times"][:, :Q], pos, axis=1)
    head = (bl["head"] + give_l) % Q
    count = bl["count"] - give_l
    # global donation pool in deterministic rank order
    don = _gat(don_l)                                       # (S, K)
    validd = k[None, :] < give[:, None]
    ranks = jnp.where(validd, gcum[:, None] + k[None, :], S * K)
    pool = jnp.zeros((S * K + 1,)).at[ranks.reshape(-1)].set(
        jnp.where(validd, don, 0.0).reshape(-1))[:S * K]
    # receivers claim consecutive ranks and append at their tail
    validc = k[None, :] < take_l[:, None]
    incoming = pool[jnp.where(validc, tcum_l[:, None] + k[None, :], 0)]
    rows = jnp.arange(Sl)[:, None]
    posr = (head[:, None] + count[:, None] + k[None, :]) % Q
    times = bl["times"].at[rows, jnp.where(validc, posr, Q)].set(
        jnp.where(validc, incoming, 0.0))
    new_bl = dict(times=times, head=head, count=count + take_l)

    def _move_ring(ring, fill):
        # an extra identity ring (request uid, and in serve+lm mode the
        # label/difficulty/embedding bound at arrival) rides the identical
        # donation plan so a stolen backlog entry keeps its task identity.
        # Scalar rings are (Sl, Q+1); the embedding ring carries a
        # trailing feature axis, hence the broadcastable mask/pool shapes
        trail = ring.shape[2:]
        px = pos[..., None] if trail else pos
        vd = validd[..., None] if trail else validd
        vc = validc[..., None] if trail else validc
        don_r = _gat(jnp.take_along_axis(ring[:, :Q], px, axis=1))
        pool_r = jnp.full((S * K + 1,) + trail, fill, ring.dtype).at[
            ranks.reshape(-1)].set(
            jnp.where(vd, don_r, fill).reshape((-1,) + trail))[:S * K]
        inc_r = pool_r[jnp.where(validc, tcum_l[:, None] + k[None, :], 0)]
        return ring.at[rows, jnp.where(validc, posr, Q)].set(
            jnp.where(vc, inc_r, fill))

    for name, fill in (("uid", -1), ("tlab", 0), ("diff", 1.0),
                       ("feat", 0.0)):
        if name in bl:
            new_bl[name] = _move_ring(bl[name], fill)
    return new_bl, take_l, give_l


# --------------------------------------------------------------------------
# driver: one state constructor and one step for the simulator's scan
# (``_run_one``) and the live serve tick (``_serve_tick_impl``)
# --------------------------------------------------------------------------
#
# A refactor here is held to the StableHLO text of every router program
# (``tools/hlo_digests.py``), so code keeps the order it makes values in:
# ``_init_state`` builds the caller's ``extra()`` between the shard and the
# learner state; the FIFO push makes each ring's value as it reaches it.

# the per-shard subtrees of the tick state, split over the "shard" mesh
# axis by both ``shard_map`` wrappers
_SHARDED_KEYS = ("ws", "banks", "win", "bl", "seeds")


class _Mesh(NamedTuple):
    """A tick's place on the ``("shard",)`` mesh: ``Sl`` local shard
    groups from global shard ``lo``. ``gather`` all-gathers a per-shard
    array in canonical shard order, ``gsum`` sums it over every shard;
    both stay local when ``axis_name`` is None."""
    Sl: int
    lo: object
    axis_name: Optional[str]

    def gather(self, x):
        if self.axis_name is None:
            return x
        return jax.lax.all_gather(x, self.axis_name, axis=0, tiled=True)

    def gsum(self, x):
        v = x.sum()
        return v if self.axis_name is None else jax.lax.psum(v, self.axis_name)


def _mesh(cfg: StreamConfig, axis_name=None) -> _Mesh:
    if axis_name is None:
        return _Mesh(cfg.n_shards, 0, None)
    Sl = cfg.n_shards // cfg.sharding.n_devices
    return _Mesh(Sl, jax.lax.axis_index(axis_name) * Sl, axis_name)


def _learner_tick_params(cfg: StreamConfig, state):
    """Per-tick learner parameters read from the replicated tick state."""
    L = cfg.learner
    if L.enabled:
        lW, lb = state["learn"].W, state["learn"].b
        # fusion weight ramps with the training-set size so an
        # untrained model contributes nothing to finalization
        fuse_w = L.prior_scale * jnp.minimum(
            1.0, state["buf_n"].astype(jnp.float32) / L.ramp_n)
    else:
        lW = jnp.zeros((1, cfg.n_classes))
        lb = jnp.zeros((cfg.n_classes,))
        fuse_w = jnp.zeros(())
    if cfg.routing.admission == "uncertain_learnable":
        gW, gb = state["learn2"].W, state["learn2"].b
    else:
        gW = jnp.zeros((2, 2))
        gb = jnp.zeros((2,))
    return lW, lb, fuse_w, gW, gb


def _learner_push_fit(cfg: StreamConfig, state, train, step, gat):
    """Push this tick's finalized examples into the replay ring and run the
    cadenced online fit; returns the dict of state updates (empty when the
    learner is off). The learner is SHARED across shards: the training tree
    is all-gathered into canonical shard order first, so every device
    pushes the identical examples and fits the identical replicated model.
    """
    from repro.learning import linear

    L = cfg.learner
    if not L.enabled:
        return {}
    B = L.buffer
    train = jax.tree_util.tree_map(gat, train)
    tm = train["mask"].reshape(-1)
    tf = train["feat"].reshape(-1, L.n_features)
    tl = train["label"].reshape(-1)
    rank = (jnp.cumsum(tm) - 1).astype(jnp.int32)
    pos = jnp.where(tm, (state["buf_n"] + rank) % B, B)
    buf_X = state["buf_X"].at[pos].set(
        jnp.where(tm[:, None], tf, state["buf_X"][pos]))
    buf_y = state["buf_y"].at[pos].set(
        jnp.where(tm, tl, state["buf_y"][pos]))
    buf_n = state["buf_n"] + tm.sum()
    learn = jax.lax.cond(
        (step % L.fit_every == 0) & (buf_n > 0),
        lambda l: linear.fit(
            l, buf_X[:B], buf_y[:B],
            (jnp.arange(B) < buf_n).astype(jnp.float32),
            steps=L.fit_steps, lr=L.lr, l2=L.l2, fresh_opt=False),
        lambda l: l, state["learn"])
    upd = dict(learn=learn, buf_X=buf_X, buf_y=buf_y, buf_n=buf_n)
    if cfg.routing.admission == "uncertain_learnable":
        # learnability head trains on the SAME ring positions with
        # the binary finalized-confident target, square-augmented
        # features, identical cadence
        tt = train["learnable"].reshape(-1)
        buf_t = state["buf_t"].at[pos].set(
            jnp.where(tm, tt, state["buf_t"][pos]))
        # the head is tiny (2F x 2) and its score gates every
        # admission, so unlike the main learner it is REFIT FROM
        # SCRATCH on the current ring each cadence: its target
        # distribution shifts hard at cold start (nothing is
        # model-known, every target 0) and Adam momentum carried
        # across that shift leaves the online head stuck far from
        # the batch optimum. A fresh 60-step fit on <= buffer
        # examples costs microseconds per cadence tick
        learn2 = jax.lax.cond(
            (step % L.fit_every == 0) & (buf_n > 0),
            lambda l: linear.fit(
                linear.init(2 * L.n_features, 2),
                learnability_features(buf_X[:B]), buf_t[:B],
                (jnp.arange(B) < buf_n).astype(jnp.float32),
                steps=60, lr=L.lr, l2=L.l2),
            lambda l: l, state["learn2"])
        upd.update(learn2=learn2, buf_t=buf_t)
    return upd


def _init_state(cfg: StreamConfig, k_init, k_seed, mesh: _Mesh, pop=None,
                extra=dict):
    """The tick state both drivers start from, from the caller's keys:
    clock, step, the per-shard counter ``seeds`` and shard state (``ws``,
    ``banks``, ``win``, ``bl``) of the local shard groups (drawn full
    width, then sliced), the caller's ``extra()`` entries, and the
    learner state shared across shards."""
    from repro.learning import linear

    S, L = cfg.n_shards, cfg.learner
    # typed keys travel as key_data: extended dtypes don't support
    # dynamic_slice
    init_kd = jax.random.key_data(jax.random.split(k_init, S))
    seeds = jax.random.bits(k_seed, (S,), jnp.uint32)
    if mesh.axis_name is not None:
        init_kd = jax.lax.dynamic_slice_in_dim(init_kd, mesh.lo, mesh.Sl)
        seeds = jax.lax.dynamic_slice_in_dim(seeds, mesh.lo, mesh.Sl)
    ws, banks, win, bl = jax.vmap(
        lambda kd: _init_shard(cfg, jax.random.wrap_key_data(kd),
                               pop))(init_kd)
    state = dict(t=jnp.zeros(()), step=jnp.zeros((), jnp.int32),
                 seeds=seeds, ws=ws, banks=banks, win=win, bl=bl)
    state.update(extra())
    if L.enabled:
        # replay ring of finalized (features, label) pairs, +1 dump row
        state["learn"] = linear.init(L.n_features, cfg.n_classes)
        state["buf_X"] = jnp.zeros((L.buffer + 1, L.n_features))
        state["buf_y"] = jnp.zeros((L.buffer + 1,), jnp.int32)
        state["buf_n"] = jnp.zeros((), jnp.int32)
    if cfg.routing.admission == "uncertain_learnable":
        # the learnability head: linear over square-augmented features
        # (routing.learnability_features), binary target "did the model's
        # prediction agree with the crowd's final label?" stored alongside
        # the replay ring (see the target rationale in _shard_tick)
        state["learn2"] = linear.init(2 * L.n_features, 2)
        state["buf_t"] = jnp.zeros((L.buffer + 1,), jnp.int32)
    return state


def _tick_step(cfg: StreamConfig, state, n_arr, seeds, mesh: _Mesh,
               warmup_t, uid_base=None, feat_in=None, labels_in=None,
               **shard_kw):
    """One tick of the local shard groups: learner parameters, one
    ``vmap`` of ``_shard_tick`` (``n_arr``, ``seeds`` and the serve-mode
    injections are per-shard, None where absent), stealing, the learner's
    push and fit, and the clock. Returns ``(new, metrics, got, gave)``."""
    t, step = state["t"], state["step"]
    with jax.named_scope("learner"):
        lW, lb, fuse_w, gW, gb = _learner_tick_params(cfg, state)
    ws, win, bl, m, train = jax.vmap(
        lambda w, bk, wi, b, na, sd, ub, fi, li: _shard_tick(
            cfg, w, bk, wi, b, na, t, step, sd, warmup_t, lW, lb, fuse_w,
            gW, gb, uid_base=ub, feat_in=fi, labels_in=li, **shard_kw),
    )(state["ws"], state["banks"], state["win"], state["bl"], n_arr, seeds,
      uid_base, feat_in, labels_in)

    with jax.named_scope("steal"):
        if cfg.sharding.steal != "none":
            bl, got, gave = _steal_rebalance(cfg, bl, mesh)
        else:
            got = gave = jnp.zeros((mesh.Sl,), jnp.int32)

    new = dict(state)
    with jax.named_scope("learner"):
        new.update(_learner_push_fit(cfg, state, train, step, mesh.gather))
    new.update(t=t + cfg.dt, step=step + 1, ws=ws, win=win, bl=bl)
    return new, m, got, gave


def _run_one(cfg: StreamConfig, horizon: int, key, warmup_t, rate_scale,
             cap_eff=None, axis_name=None, traced=None, bank=None):
    """One replication of the streaming service.

    ``axis_name`` switches on device sharding: the function then runs
    INSIDE ``shard_map`` over a 1-D mesh of ``cfg.sharding.n_devices``
    devices, each owning ``n_shards / n_devices`` shard groups. Everything
    derived from ``key`` (init keys, counter seeds, arrivals, shard
    assignment) is computed replicated and sliced locally, per-shard
    metrics accumulate in the carry and are all-gathered back into
    canonical shard order before the final reduction — so the reduction
    code (and its float summation order) is IDENTICAL for every device
    count, which is what pins single-device bit-parity. ``cap_eff`` is the
    traced effective vote budget for the masked votes-cap sweep;
    ``traced`` is a :class:`StreamTraced` bundle of absolute overrides
    (grid path) — it subsumes ``cap_eff`` and the arrival rate and routes
    the Beta accuracy params into the worker-bank init."""
    rate_abs, pop, ph_t, hs_t = None, None, None, None
    if traced is not None:
        cap_eff = jnp.where(traced.votes_cap > 0,
                            traced.votes_cap,
                            cfg.policy.votes_cap).astype(jnp.int32)
        rate_abs = jnp.where(traced.rate > 0, traced.rate,
                             jnp.float32(cfg.arrivals.rate))
        pop = PopTraced(acc_a=jnp.asarray(traced.acc_a, jnp.float32),
                        acc_b=jnp.asarray(traced.acc_b, jnp.float32))
        # difficulty mixture overrides use a NEGATIVE sentinel (0.0 is a
        # valid p_hard); resolved here so each grid cell traces its own
        # hard fraction / score scale through the admission draws
        ph_t = jnp.where(traced.p_hard >= 0, traced.p_hard,
                         jnp.float32(cfg.p_hard))
        hs_t = jnp.where(traced.hard_scale >= 0, traced.hard_scale,
                         jnp.float32(cfg.hard_scale))

    S = cfg.n_shards
    mesh = _mesh(cfg, axis_name)
    Sl, _gat, _gsum = mesh.Sl, mesh.gather, mesh.gsum
    k_init, k_seed, k_run = jax.random.split(key, 3)
    tr = cfg.trace
    tr_ph = tr is not None and tr.phases
    tr_pt = tr is not None and tr.per_tick

    def accumulators():
        # the run's own carry: its key and arrival process, and per-shard
        # steady-state accumulators
        zi = lambda: jnp.zeros((Sl,), jnp.int32)
        acc = dict(
            key=k_run, arr=init_arrival_state(cfg.arrivals),
            hist=jnp.zeros((Sl, cfg.tis_bins), jnp.int32),
            done=zi(), correct=zi(),
            sum_tis=jnp.zeros((Sl,)), votes_fin=zi(),
            completions=zi(), done_all=zi(), dropped=zi(),
            stolen=zi(), donated=zi(),
            over=jnp.zeros((), jnp.int32),
            arrived=jnp.zeros((), jnp.int32),
            arrived_warm=jnp.zeros((), jnp.int32),
            model_known=zi(),
        )
        if tr_ph:
            for pk in TRACE_PHASES:
                acc["ph_" + pk] = jnp.zeros((Sl, cfg.tis_bins), jnp.int32)
                acc["ps_" + pk] = jnp.zeros((Sl,))
        return acc

    state = _init_state(cfg, k_init, k_seed, mesh, pop, accumulators)
    # the seeds stay out of the scan carry: every tick reads the same ones
    seeds = state.pop("seeds")
    M, cap_total = cfg.max_arrivals_per_tick, cfg.max_arrivals_per_tick * S

    def tick(state, _):
        t = state["t"]
        key, k_arr, k_sid = jax.random.split(state["key"], 3)
        warm = t >= warmup_t
        # arrivals + shard assignment are REPLICATED draws (every device
        # samples the same stream from the same key); each device then
        # slices out its own shard group's arrival counts
        n_new, arr, _rate = sample_arrivals(cfg.arrivals, state["arr"],
                                            k_arr, t, cfg.dt, rate_scale,
                                            rate_abs)
        n_cap = jnp.minimum(n_new, cap_total)
        sid = jax.random.randint(k_sid, (cap_total,), 0, S)
        valid = jnp.arange(cap_total) < n_cap
        n_arr = jnp.zeros((S + 1,), jnp.int32).at[
            jnp.where(valid, sid, S)].add(1)[:S]
        over = (n_arr - M).clip(0).sum() + (n_new - n_cap)
        n_arr = jnp.minimum(n_arr, M)
        if axis_name is not None:
            n_arr = jax.lax.dynamic_slice_in_dim(n_arr, mesh.lo, Sl)

        new, m, got, gave = _tick_step(
            cfg, state, n_arr, seeds, mesh, warmup_t, cap_eff=cap_eff,
            p_hard_t=ph_t, hard_scale_t=hs_t, bank=bank)
        new.update(
            key=key, arr=arr,
            hist=state["hist"] + m["hist"],
            done=state["done"] + m["done"],
            correct=state["correct"] + m["correct"],
            sum_tis=state["sum_tis"] + m["sum_tis"],
            votes_fin=state["votes_fin"] + m["votes_fin"],
            completions=state["completions"] + m["completions"],
            done_all=state["done_all"] + m["done_all"],
            dropped=state["dropped"] + m["dropped"],
            stolen=state["stolen"] + got,
            donated=state["donated"] + gave,
            over=state["over"] + over,
            arrived=state["arrived"] + n_new,
            arrived_warm=state["arrived_warm"] + jnp.where(warm, n_new, 0),
            model_known=state["model_known"] + m["model_known"],
        )
        if tr_ph:
            new.update({"ph_" + pk: state["ph_" + pk] + m["ph_" + pk]
                        for pk in TRACE_PHASES})
            new.update({"ps_" + pk: state["ps_" + pk] + m["ps_" + pk]
                        for pk in TRACE_PHASES})
        ys = dict(arrivals=n_new, finalized=_gsum(m["done_all"]),
                  backlog=_gsum(m["backlog"]), in_flight=_gsum(m["in_flight"]))
        if tr_pt:
            # per-tick activity series (cross-shard reduced, so the series
            # is identical at any device count)
            ys["votes"] = _gsum(m["votes"])
            ys["busy_workers"] = _gsum(m["busy_workers"])
            ys["idle_workers"] = _gsum(m["idle_workers"])
            ys["dropped"] = _gsum(m["dropped"])
            ys["stolen"] = _gsum(got)
            ys["donated"] = _gsum(gave)
            if cfg.routing.admission != "fifo":
                ys["adm_score"] = _gsum(m["adm_score"]) / S
        return new, ys

    state, ys = jax.lax.scan(tick, state, None, length=horizon)
    # per-shard accumulators, reduced over the GATHERED canonical shard
    # order so sharded and unsharded runs execute the identical reduction
    local = {k: state[k] for k in
             ("hist", "done", "correct", "sum_tis", "votes_fin",
              "completions", "done_all", "dropped", "stolen", "donated",
              "model_known")}
    if tr_ph:
        # per-phase histograms/sums ride the same gather-then-reduce path
        # as every other per-shard accumulator, so the sharded trace is
        # all-gathered to canonical shard order and bit-identical to the
        # single-device reduction
        for pk in TRACE_PHASES:
            local["ph_" + pk] = state["ph_" + pk]
            local["ps_" + pk] = state["ps_" + pk]
    local["cost_wait"] = state["ws"]["cost_wait"]      # (S_local,) scalars
    local["cost_work"] = state["ws"]["cost_work"]
    local["n_churned"] = state["ws"]["n_churned"]
    local["n_evicted"] = state["ws"]["n_evicted"]
    local["backlog_end"] = state["bl"]["count"]
    local["in_flight_end"] = state["win"]["active"].sum(-1)
    full = jax.tree_util.tree_map(_gat, local)              # (S, ...)
    out = {k: v.sum(0) for k, v in full.items()}
    out["dropped"] = out["dropped"] + state["over"]
    out["arrived"] = state["arrived"]
    out["arrived_warm"] = state["arrived_warm"]
    if "learn2" in state:
        # final learnability-head params (diagnostics: lets callers probe
        # what the admission score learned about the feature space)
        out["learn2_W"] = state["learn2"].W
        out["learn2_b"] = state["learn2"].b
    # physically device-local shard diagnostics (under shard_map these
    # leave the mesh sharded over "shard"; see _run_sharded_jit out_specs)
    out["per_shard"] = {k: local[k] for k in
                        ("backlog_end", "in_flight_end", "stolen", "donated")}
    out["series"] = ys
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _run_jit(cfg: StreamConfig, horizon: int, keys, warmup_t, rate_scale,
             bank):
    return jax.vmap(
        lambda k: _run_one(cfg, horizon, k, warmup_t, rate_scale,
                           bank=bank))(keys)


def _bank_for(cfg: StreamConfig):
    """Device-resident embedding-bank features for ``feature_kind="lm"``
    (host-side, cached per config). None on the Gaussian path — the
    compiled program is then exactly the pre-embed program."""
    if cfg.learner.feature_kind != "lm":
        return None
    from repro.embed.bank import embedding_bank
    return embedding_bank(cfg.learner.embed, cfg.n_classes,
                          cfg.learner.n_features, cfg.learner.class_sep,
                          cfg.learner.hard_sep_scale).feats


def _shard_specs(tree: dict, keys=_SHARDED_KEYS, axis: int = 0):
    """``shard_map`` specs for a dict: leaves under ``keys`` split over
    "shard" on ``axis`` (those without it replicate), the rest replicated."""
    from jax.sharding import PartitionSpec as Pspec

    return {k: jax.tree_util.tree_map(
        lambda x: Pspec(*[None] * axis, "shard")
        if k in keys and x.ndim > axis else Pspec(), v)
        for k, v in tree.items()}


def _sharded_jit(cfg: StreamConfig, body, bank, in_specs, out_specs):
    """``body(bank, *args)`` jitted over the ``("shard",)`` mesh, first
    argument donated. The lm bank (a per-config constant, built by the
    caller outside any trace) is closed over, replicated, so the gaussian
    program's signature is untouched."""
    from repro.launch.mesh import check_stream_sharding, make_stream_mesh

    D = cfg.sharding.n_devices
    check_stream_sharding(cfg.n_shards, D)
    fn = jax.shard_map(functools.partial(body, bank),
                       mesh=make_stream_mesh(D), in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _run_sharded_jit(cfg: StreamConfig, horizon: int):
    """Compiled shard_map-partitioned runner for ``cfg.sharding.n_devices``.

    Inputs are replicated (keys travel as key_data; extended dtypes can't
    cross the shard_map boundary); all per-shard state lives sharded
    inside — the scan carry keeps window/pool/backlog device-resident
    between ticks, nothing round-trips to host — and the keys buffer is
    donated. Reduced metrics come out replicated; the ``per_shard``
    diagnostics stay physically sharded over the "shard" axis."""
    from jax.sharding import PartitionSpec as Pspec

    def body(bank, keys_data, warmup_t, rate_scale):
        keys = jax.random.wrap_key_data(keys_data)
        return jax.vmap(
            lambda k: _run_one(cfg, horizon, k, warmup_t, rate_scale,
                               axis_name="shard", bank=bank))(keys)

    bank = _bank_for(cfg)       # outside any trace: it is cached
    # output structure from an abstract single-device trace; per_shard is
    # sharded on axis 1, after the replication axis
    shapes = jax.eval_shape(
        lambda k, w, r: jax.vmap(
            lambda kk: _run_one(cfg, horizon, kk, w, r, bank=bank))(k),
        jax.random.split(jax.random.key(0), 1), 0.0, 1.0)
    return _sharded_jit(cfg, body, bank, (Pspec(), Pspec(), Pspec()),
                        _shard_specs(shapes, ("per_shard",), axis=1))


def _as_stream_config(cfg, serve: bool = False) -> StreamConfig:
    """Accept a StreamConfig or a declarative ``repro.scenarios``
    ScenarioSpec, compiled through the unified spec layer
    (``to_serve_config`` with ``serve``)."""
    if isinstance(cfg, StreamConfig):
        return cfg
    from repro.scenarios.compile import to_serve_config, to_stream_config
    return to_serve_config(cfg) if serve else to_stream_config(cfg)


_as_serve_config = functools.partial(_as_stream_config, serve=True)


def _validate_stream_config(cfg: StreamConfig, serve: bool = False):
    """Reject a config its driver cannot run: ``serve`` names the driver
    (``serve_init``/``serve_tick``, else the ``run_stream*`` simulators)
    and must match ``cfg.serve``."""
    if cfg.serve != serve:
        raise ValueError(
            "serve_init/serve_tick require StreamConfig.serve=True "
            "(compile the scenario through "
            "repro.scenarios.compile.to_serve_config)" if serve else
            "StreamConfig.serve=True is the live-injection mode: drive it "
            "one tick at a time via serve_init/serve_tick (repro.serving."
            "server), not through the run_stream* simulators")
    if cfg.learner.enabled and cfg.learner.n_features < cfg.n_classes:
        raise ValueError("learner.n_features must be >= n_classes "
                         "(one-hot class means)")
    L = cfg.learner
    if L.feature_kind not in ("gaussian", "lm"):
        raise ValueError("learner.feature_kind must be 'gaussian' or 'lm', "
                         f"got {L.feature_kind!r}")
    if L.feature_kind == "lm":
        if not L.enabled:
            raise ValueError(
                "learner.feature_kind='lm' requires learner.enabled: LM "
                "embeddings exist to feed the learner/fusion path")
        if L.embed is None:
            raise ValueError(
                "learner.feature_kind='lm' requires learner.embed (an "
                "EmbedConfig; the scenario layer lowers spec.embed into it)")
        if L.embed.projection_dim is not None \
                and L.embed.projection_dim != L.n_features:
            raise ValueError(
                f"learner.embed.projection_dim={L.embed.projection_dim} "
                f"must equal learner.n_features={L.n_features} (the "
                "projection target IS the learner feature width)")
        if L.embed.bank_size % (2 * cfg.n_classes) != 0:
            raise ValueError(
                f"learner.embed.bank_size={L.embed.bank_size} must be a "
                f"positive multiple of 2 * n_classes = {2 * cfg.n_classes}")
    elif L.embed is not None:
        raise ValueError("learner.embed is set but feature_kind="
                         f"{L.feature_kind!r}; an embedding config without "
                         "the lm feature path is a misconfiguration")
    if cfg.routing.admission not in ("fifo", "uncertain",
                                     "uncertain_learnable"):
        raise ValueError("routing.admission must be 'fifo', 'uncertain' or "
                         "'uncertain_learnable', "
                         f"got {cfg.routing.admission!r}")
    if cfg.routing.admission != "fifo" and not cfg.learner.enabled:
        raise ValueError(f"routing.admission={cfg.routing.admission!r} "
                         "requires learner.enabled: features are drawn at "
                         "arrival and ranked by the online model")
    sh = cfg.sharding
    if sh.steal not in ("none", "pressure"):
        raise ValueError("sharding.steal must be 'none' or 'pressure', "
                         f"got {sh.steal!r}")
    if sh.steal != "none":
        if cfg.routing.admission != "fifo":
            raise ValueError(
                f"sharding.steal={sh.steal!r} rebalances the FIFO backlog "
                "ring and requires routing.admission='fifo', got "
                f"{cfg.routing.admission!r}")
        if not 1 <= sh.steal_max <= cfg.backlog:
            raise ValueError("sharding.steal_max must be in [1, backlog="
                             f"{cfg.backlog}], got {sh.steal_max}")
        if sh.steal_slack < 0:
            raise ValueError("sharding.steal_slack must be >= 0, got "
                             f"{sh.steal_slack}")
    if sh.n_devices > 1:
        from repro.launch.mesh import check_stream_sharding
        check_stream_sharding(cfg.n_shards, sh.n_devices)


def run_stream(cfg, horizon: int, *, n_reps: int = 1,
               seed: int = 0, warmup_frac: float = 0.3,
               rate_scale: float = 1.0):
    """Run ``n_reps`` replications of the streaming service for ``horizon``
    ticks. ``cfg`` is a StreamConfig or a ``repro.scenarios.ScenarioSpec``.
    Steady-state metrics (histogram, counters) only accumulate after
    ``warmup_frac`` of the horizon. ``rate_scale`` multiplies the offered
    arrival rate WITHOUT recompiling (it is traced), so load sweeps are
    one compilation. Returns stacked device arrays with leading dim n_reps
    plus ``warmup_t``/``measured_s`` scalars."""
    cfg = _as_stream_config(cfg)
    _validate_stream_config(cfg)
    keys = jax.random.split(jax.random.key(seed), n_reps)
    warmup_t = float(warmup_frac * horizon * cfg.dt)
    if cfg.sharding.n_devices > 1:
        out = _run_sharded_jit(cfg, int(horizon))(
            jax.random.key_data(keys), jnp.float32(warmup_t),
            jnp.float32(rate_scale))
    else:
        out = _run_jit(cfg, int(horizon), keys, warmup_t,
                       jnp.float32(rate_scale), _bank_for(cfg))
    out = dict(out)
    out["warmup_t"] = warmup_t
    out["measured_s"] = horizon * cfg.dt - warmup_t
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _run_swept(cfg: StreamConfig, horizon: int, keys, warmup_t, rate_scales,
               bank):
    return jax.vmap(lambda rs: jax.vmap(
        lambda k: _run_one(cfg, horizon, k, warmup_t, rs,
                           bank=bank))(keys))(rate_scales)


@functools.partial(jax.pmap, static_broadcasted_argnums=(0, 1),
                   in_axes=(None, None, None, None, 0, None))
def _run_swept_pmap(cfg: StreamConfig, horizon: int, keys, warmup_t,
                    rate_scales, bank):
    return jax.vmap(lambda rs: jax.vmap(
        lambda k: _run_one(cfg, horizon, k, warmup_t, rs,
                           bank=bank))(keys))(rate_scales)


def run_stream_sweep(cfg, horizon: int, rate_scales, *, n_reps: int = 1,
                     seed: int = 0, warmup_frac: float = 0.3,
                     shard: bool = True):
    """One-compilation load sweep: ``vmap`` over the offered-rate scales on
    top of the replication vmap, so every sweep point advances in lock-step
    inside a single jitted program (the ``repro.scenarios.sweep`` backend
    for the stream engine's arrival-rate axis). With ``shard`` (default)
    and more than one visible device, the traced sweep axis is additionally
    pmap-sharded across devices (mesh plumbing shared with the sharded
    tick): sweep points are padded to a device multiple, split round-robin,
    and the pad rows dropped. Returns stacked arrays with leading dims
    ``(len(rate_scales), n_reps)``."""
    cfg = _as_stream_config(cfg)
    _validate_stream_config(cfg)
    keys = jax.random.split(jax.random.key(seed), n_reps)
    warmup_t = float(warmup_frac * horizon * cfg.dt)
    scales = jnp.asarray(rate_scales, jnp.float32)
    V = int(scales.shape[0])
    D = jax.local_device_count()
    bank = _bank_for(cfg)
    if shard and D > 1 and V > 1:
        pad = (-V) % D
        if pad:
            scales = jnp.concatenate(
                [scales, jnp.broadcast_to(scales[-1:], (pad,))])
        out = _run_swept_pmap(cfg, int(horizon), keys, warmup_t,
                              scales.reshape(D, -1), bank)
        out = jax.tree_util.tree_map(
            lambda v: v.reshape((V + pad,) + v.shape[2:])[:V], out)
    else:
        out = _run_swept(cfg, int(horizon), keys, warmup_t, scales, bank)
    out = dict(out)
    out["warmup_t"] = warmup_t
    out["measured_s"] = horizon * cfg.dt - warmup_t
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _run_capswept(cfg: StreamConfig, horizon: int, keys, warmup_t, caps,
                  rate_scale, bank):
    return jax.vmap(lambda c: jax.vmap(
        lambda k: _run_one(cfg, horizon, k, warmup_t, rate_scale,
                           cap_eff=c, bank=bank))(keys))(caps)


def run_stream_votes_sweep(cfg, horizon: int, votes_caps, *, n_reps: int = 1,
                           seed: int = 0, warmup_frac: float = 0.3,
                           rate_scale: float = 1.0):
    """One-compilation votes-cap sweep via MASKED caps.

    The vote buffers are sized statically at ``max(votes_caps)`` and a
    traced effective cap gates vote admission, finalization and the
    outstanding-vote target (``_shard_tick``'s ``cap_eff``), so every
    swept value shares one jitted program. Columns past a point's
    effective cap are never written or read, which is why each sweep point
    is bit-for-bit equal to a standalone ``run_stream`` at that
    ``votes_cap`` (tests/test_sharding.py pins it). Returns stacked arrays
    with leading dims ``(len(votes_caps), n_reps)``."""
    cfg = _as_stream_config(cfg)
    caps = [int(v) for v in votes_caps]
    if not caps:
        raise ValueError("votes_caps must be non-empty")
    for v in caps:
        if v < max(1, cfg.policy.min_votes):
            raise ValueError(
                f"votes_cap sweep value {v} must be >= max(1, "
                f"policy.min_votes={cfg.policy.min_votes})")
    cfg = dataclasses.replace(
        cfg, policy=dataclasses.replace(cfg.policy, votes_cap=max(caps)))
    _validate_stream_config(cfg)
    keys = jax.random.split(jax.random.key(seed), n_reps)
    warmup_t = float(warmup_frac * horizon * cfg.dt)
    out = _run_capswept(cfg, int(horizon), keys, warmup_t,
                        jnp.asarray(caps, jnp.int32), jnp.float32(rate_scale),
                        _bank_for(cfg))
    out = dict(out)
    out["warmup_t"] = warmup_t
    out["measured_s"] = horizon * cfg.dt - warmup_t
    return out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _run_grid_jit(cfg: StreamConfig, horizon: int, keys, warmup_t, traced,
                  bank):
    return jax.vmap(lambda tr: jax.vmap(
        lambda k: _run_one(cfg, horizon, k, warmup_t, jnp.float32(1.0),
                           traced=tr, bank=bank))(keys))(traced)


@functools.partial(jax.pmap, static_broadcasted_argnums=(0, 1),
                   in_axes=(None, None, None, None, 0, None))
def _run_grid_pmap(cfg: StreamConfig, horizon: int, keys, warmup_t, traced,
                   bank):
    return jax.vmap(lambda tr: jax.vmap(
        lambda k: _run_one(cfg, horizon, k, warmup_t, jnp.float32(1.0),
                           traced=tr, bank=bank))(keys))(traced)


def run_stream_grid(cfg, horizon: int, traced: StreamTraced, *,
                    n_reps: int = 1, seed: int = 0,
                    warmup_frac: float = 0.3, shard: bool = True,
                    timing_name: str = None):
    """Multi-axis one-compilation grid over a :class:`StreamTraced` bundle.

    ``traced`` leaves share a leading cell axis ``(V,)`` (scalars
    broadcast); each cell runs the full streaming service with that cell's
    absolute overrides — any subset of {arrival rate, votes cap, Beta
    accuracy params} varies across cells under ONE compilation. This is
    the ``repro.grid`` backend for the stream engine: a cell whose traced
    values equal the static config is bit-for-bit a standalone
    ``run_stream`` (vote buffers are sized at the static ``votes_cap``,
    exactly the masked-cap program of ``run_stream_votes_sweep``).

    With multiple local devices and ``shard=True`` the cell axis is
    pmapped (cells padded to a device multiple repeating the last cell,
    split ``(D, V/D)``, padding dropped on the way out). Device-sharded
    single runs (``sharding.n_devices > 1``) are rejected — the mesh is
    spent on grid cells here. ``timing_name`` routes an AOT
    lower/compile + execute split through ``repro.obs.timing``. Returns
    stacked arrays with leading dims ``(V, n_reps)``.
    """
    cfg = _as_stream_config(cfg)
    _validate_stream_config(cfg)
    if cfg.sharding.n_devices > 1:
        raise ValueError(
            "run_stream_grid batches grid cells across devices and cannot "
            "also shard_map single runs; use sharding.n_devices=1 (run "
            "device-sharded scenarios per-cell via run_stream)")
    lo = max(1, cfg.policy.min_votes)
    for v in np.atleast_1d(np.asarray(traced.votes_cap)):
        if v != 0 and not lo <= int(v) <= cfg.policy.votes_cap:
            raise ValueError(
                f"grid votes_cap value {int(v)} must be 0 (unset) or in "
                f"[max(1, policy.min_votes)={lo}, "
                f"policy.votes_cap={cfg.policy.votes_cap}]")
    for v in np.atleast_1d(np.asarray(traced.p_hard)):
        if v > 1.0:
            raise ValueError(
                f"grid p_hard value {float(v)} must be negative (unset) "
                "or in [0, 1]")
    V = max([int(np.asarray(leaf).shape[0]) for leaf in traced
             if np.ndim(leaf) > 0] or [1])
    dt_ = dict(rate=jnp.float32, votes_cap=jnp.int32,
               acc_a=jnp.float32, acc_b=jnp.float32,
               p_hard=jnp.float32, hard_scale=jnp.float32)
    traced = StreamTraced(**{
        f: jnp.broadcast_to(jnp.asarray(getattr(traced, f), dt_[f]), (V,))
        for f in StreamTraced._fields})
    keys = jax.random.split(jax.random.key(seed), n_reps)
    warmup_t = float(warmup_frac * horizon * cfg.dt)
    D = jax.local_device_count()
    bank = _bank_for(cfg)
    if shard and D > 1 and V >= D:
        pad = (-V) % D
        padded = StreamTraced(*[
            jnp.concatenate([leaf, jnp.broadcast_to(leaf[-1:], (pad,))])
            .reshape(D, -1) for leaf in traced])
        out = _aot_timed(_run_grid_pmap, timing_name, 2,
                         cfg, int(horizon), keys, jnp.float32(warmup_t),
                         padded, bank)
        out = jax.tree_util.tree_map(
            lambda v: v.reshape((V + pad,) + v.shape[2:])[:V], out)
    else:
        out = _aot_timed(_run_grid_jit, timing_name, 2,
                         cfg, int(horizon), keys, jnp.float32(warmup_t),
                         traced, bank)
    out = dict(out)
    out["warmup_t"] = warmup_t
    out["measured_s"] = horizon * cfg.dt - warmup_t
    return out


def _hist_percentile(hist, q, bin_s):
    """Right-edge percentile from the pooled time-in-system histogram.

    The top bin collects every task clipped past the histogram range, so a
    percentile landing there is unbounded above — report it as ``inf``
    rather than silently truncating to the ceiling (an overloaded run must
    not masquerade as one with a bounded tail). An EMPTY histogram (no
    task finalized in the measured interval — routine at warmup or under
    total overload) is also ``inf``, not NaN: NaN silently poisons every
    downstream comparison (a NaN p95 "passes" no budget gate but also
    fails no assertion loudly), while ``inf`` reads as what it is — no
    evidence of a bounded tail."""
    hist = np.asarray(hist)
    if hist.size == 0:
        return float("inf")
    c = np.cumsum(hist)
    if c[-1] == 0:
        return float("inf")
    idx = int(np.searchsorted(c, q / 100.0 * c[-1]))
    if idx >= len(hist) - 1:
        return float("inf")
    return (idx + 1) * bin_s


def stream_summary(cfg, out) -> dict:
    """Reduce run_stream output to the service-level quantities the bench
    reports: offered vs sustained steady-state rate, p50/p95/p99
    time-in-system, label accuracy, votes per finalized task, drops."""
    cfg = _as_stream_config(cfg)
    reps = int(np.asarray(out["done"]).shape[0])
    dur = float(out["measured_s"]) * reps
    hist = np.asarray(out["hist"]).sum(0)
    done = float(np.asarray(out["done"]).sum())
    offered = float(np.asarray(out["arrived_warm"]).sum())
    # tasks still in the pipe (window/backlog) at horizon end arrived during
    # the measured interval but had no chance to finalize; excluding them
    # from the completion denominator keeps the stability criterion honest
    # at short horizons without inflating sustained_rate itself. The credit
    # is capped at a couple of windows' worth per replication: a healthy
    # system holds at most that much in flight, so an overloaded run (whose
    # backlog grows without bound) cannot drive the denominator to the
    # clamp and report itself stable
    pipe_cap = 2.0 * cfg.n_shards * cfg.window * reps
    holdover = min(float(np.asarray(out["in_flight_end"]).sum()
                         + np.asarray(out["backlog_end"]).sum()), pipe_cap)
    s = dict(
        n_reps=reps,
        offered_rate=offered / max(dur, 1e-9),
        sustained_rate=done / max(dur, 1e-9),
        completion_ratio=done / max(offered - holdover, 1.0),
        p50_tis=_hist_percentile(hist, 50, cfg.tis_bin_s),
        p95_tis=_hist_percentile(hist, 95, cfg.tis_bin_s),
        p99_tis=_hist_percentile(hist, 99, cfg.tis_bin_s),
        mean_tis=float(np.asarray(out["sum_tis"]).sum()) / max(done, 1.0),
        accuracy=float(np.asarray(out["correct"]).sum()) / max(done, 1.0),
        votes_per_task=float(np.asarray(out["votes_fin"]).sum())
        / max(done, 1.0),
        completions_per_task=float(np.asarray(out["completions"]).sum())
        / max(done, 1.0),
        model_known_frac=float(np.asarray(out["model_known"]).sum())
        / max(done, 1.0),
        dropped=float(np.asarray(out["dropped"]).sum()),
        backlog_end=float(np.asarray(out["backlog_end"]).sum()) / reps,
        in_flight_end=float(np.asarray(out["in_flight_end"]).sum()) / reps,
        cost=float(np.asarray(out["cost_wait"] + out["cost_work"]).sum())
        / reps,
        # a percentile landing in the clipped top bin reports inf; this
        # flag distinguishes "genuinely slow" from "tis histogram too
        # short for this workload" (resize tis_bins/tis_bin_s if set)
        hist_saturated=bool(hist.size and hist[-1] > 0),
    )
    if "ph_backlog_wait" in out:
        # per-phase latency-source breakdown (TraceConfig.phases): the
        # paper's Table-1-style decomposition of where time-in-system goes
        phases = {}
        for pk in TRACE_PHASES:
            ph = np.asarray(out["ph_" + pk])
            ph = ph.reshape(-1, ph.shape[-1]).sum(0)
            phases[pk] = dict(
                mean=float(np.asarray(out["ps_" + pk]).sum()) / max(done,
                                                                    1.0),
                p50=_hist_percentile(ph, 50, cfg.tis_bin_s),
                p95=_hist_percentile(ph, 95, cfg.tis_bin_s),
                hist_saturated=bool(ph.size and ph[-1] > 0),
            )
        s["phases"] = phases
    return s


# --------------------------------------------------------------------------
# live serving: single-tick stepping with injected arrivals
# --------------------------------------------------------------------------
#
# ``repro.serving.server`` drives the router ONE tick at a time with the
# simulator's state constructor and step. Only two things differ: arrivals
# are injected (``StreamConfig.serve`` replaces the sampled arrival process
# with exact per-shard counts, and the backlog and window carry request
# uids, plus the arrival-bound task identity with lm features), and what
# leaves the device: the donated state stays resident, the injection goes
# up as one ``(2, n_shards)`` int32 array, and the ``srv_*`` finalization
# outputs come back packed into one int32 buffer (:class:`TickOut`).

class TickOut(collections.abc.Mapping):
    """``serve_tick``'s output bundle: a read-only mapping over ONE packed
    int32 buffer, which is its only pytree leaf (``jax.device_get`` moves
    it in one transfer). ``layout`` holds ``(key, shape, dtype, offset)``
    per field, in sorted key order; float32 fields travel bit-cast and
    ``fin`` as 0/1. ``out[k]`` gives the field as a host numpy array of
    its own shape and dtype (``fin`` bool, ``conf``/``tis`` float32, ``t``
    a float32 scalar), fetching and caching the buffer on first use."""

    __slots__ = ("buf", "layout", "_fields")

    def __init__(self, buf, layout):
        self.buf, self.layout, self._fields = buf, layout, None

    def _unpack(self) -> dict:
        if self._fields is None:
            host = np.asarray(self.buf)
            fields = {}
            for k, shape, dtype, off in self.layout:
                a = host[off:off + math.prod(shape)]
                a = (a != 0) if dtype == "bool" else a.view(dtype)
                a = a.reshape(shape)
                a.flags.writeable = False
                fields[k] = a
            self._fields = fields
        return self._fields

    def __getitem__(self, k):
        return self._unpack()[k]

    def __iter__(self):
        return (k for k, *_ in self.layout)

    def __len__(self):
        return len(self.layout)


jax.tree_util.register_pytree_node(
    TickOut, lambda o: ((o.buf,), o.layout),
    lambda layout, leaves: TickOut(leaves[0], layout))


def _pack_tick_out(out: dict) -> TickOut:
    """Pack the tick's ``out`` bundle into one int32 buffer (traced: the
    layout follows from the fields' shapes, so it is fixed once per
    compiled StreamConfig), fields in sorted key order as a returned dict
    would flatten. Bit-exact: bool becomes 0/1, the 4-byte fields (int32,
    float32) are bit-cast."""
    layout, words, off = [], [], 0
    for k in sorted(out):
        x = out[k]
        w = x.astype(jnp.int32) if x.dtype == jnp.bool_ \
            else jax.lax.bitcast_convert_type(x, jnp.int32)
        layout.append((k, tuple(x.shape), np.dtype(x.dtype).name, off))
        words.append(w.reshape(-1))
        off += math.prod(x.shape)
    return TickOut(jnp.concatenate(words), tuple(layout))


def serve_init(cfg, seed: int = 0):
    """Build the device-resident state for :func:`serve_tick`.

    ``cfg`` is a StreamConfig with ``serve=True`` (or a ScenarioSpec,
    compiled via ``to_serve_config``). The state is a pytree of device
    arrays; pass it to ``serve_tick`` and keep ONLY the returned state —
    the input buffers are donated. ``seed`` fixes worker-pool init and
    every per-tick draw (task identity, vote latencies, churn), so the
    label stream for a given injection schedule is deterministic."""
    cfg = _as_serve_config(cfg)
    _validate_stream_config(cfg, serve=True)
    k_init, k_seed = jax.random.split(jax.random.key(seed))
    state = _init_state(cfg, k_init, k_seed, _mesh(cfg))
    # strip weak types (scalar-filled buffers like busy_until=inf): the
    # post-tick state is strongly typed, and an aval mismatch between the
    # init state and tick-1's output would recompile the tick once more.
    # The simulator's state never crosses a jit boundary, so it keeps them
    return jax.tree_util.tree_map(
        lambda x: jax.lax.convert_element_type(x, x.dtype), state)


def _serve_tick_impl(cfg: StreamConfig, state, n_arr, uid_base,
                     feat_in=None, labels_in=None, bank=None,
                     axis_name=None):
    """One serve tick: the simulator's step (``_tick_step``) with injected
    arrival counts in place of the sampled arrival process (no warmup —
    every finalization is reported). In lm mode ``feat_in``/``labels_in``
    carry per-injection real-text embeddings and known labels (NaN rows /
    -1 mean "simulate from the bank"). Returns ``(new_state, out)``."""
    mesh = _mesh(cfg, axis_name)
    _gat, t = mesh.gather, state["t"]
    new, m, got, gave = _tick_step(
        cfg, state, n_arr, state["seeds"], mesh, jnp.float32(0.0),
        uid_base=uid_base, feat_in=feat_in, labels_in=labels_in, bank=bank)
    out = {k: _gat(m["srv_" + k])
           for k in ("fin", "uid", "label", "votes", "conf", "tis")}
    out.update(dropped=_gat(m["dropped"]), backlog=_gat(new["bl"]["count"]),
               in_flight=_gat(new["win"]["active"].sum(-1)),
               stolen=_gat(got), donated=_gat(gave), t=t + cfg.dt)
    return new, out


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _serve_tick_jit(cfg: StreamConfig, state, inj, feat_in, labels_in,
                    bank):
    """``inj`` stacks the tick's ``n_arr`` over its ``uid_base``."""
    new, out = _serve_tick_impl(cfg, state, inj[0], inj[1], feat_in=feat_in,
                                labels_in=labels_in, bank=bank)
    return new, _pack_tick_out(out)


@functools.lru_cache(maxsize=None)
def _serve_tick_sharded_jit(cfg: StreamConfig):
    """Compiled shard_map-partitioned serve tick for
    ``cfg.sharding.n_devices`` (``_sharded_jit``, as ``_run_sharded_jit``:
    per-shard state subtrees live sharded over the "shard" axis, the
    packed buffer of the gathered ``srv_*`` outputs comes out replicated,
    and the state buffers are donated tick over tick)."""
    from jax.sharding import PartitionSpec as Pspec

    def body(bank, state, inj, feat_in, labels_in):
        new, out = _serve_tick_impl(cfg, state, inj[0], inj[1],
                                    feat_in=feat_in, labels_in=labels_in,
                                    bank=bank, axis_name="shard")
        return new, _pack_tick_out(out)

    specs = _shard_specs(jax.eval_shape(functools.partial(serve_init, cfg,
                                                          0)))
    return _sharded_jit(cfg, body, _bank_for(cfg),
                        (specs, Pspec(None, "shard"), Pspec("shard"),
                         Pspec("shard")), (specs, Pspec()))


def serve_tick(cfg, state, n_arr, uid_base, feat=None, labels=None):
    """Advance the live service by ONE tick with injected arrivals.

    ``n_arr[s]`` tasks enter shard ``s`` this tick carrying uids
    ``uid_base[s] .. uid_base[s] + n_arr[s] - 1`` (the caller's per-shard
    monotonic counters; every injected uid consumes a counter slot whether
    or not it survives). Each ``n_arr[s]`` must be <=
    ``cfg.max_arrivals_per_tick``; injections beyond free backlog capacity
    are dropped from the TAIL of this tick's batch — ``out["dropped"][s]``
    counts them, so the dropped uids are exactly the last ``dropped[s]``
    of shard ``s``'s injection. ``state`` is DONATED: keep only the
    returned state. Returns ``(state, out)`` where ``out["fin"]`` masks
    the window slots finalized this tick and ``uid``/``label``/``votes``/
    ``conf``/``tis`` give their request uid, fused label, vote count,
    posterior confidence and time-in-system (leading dim n_shards), plus
    per-shard ``dropped``/``backlog``/``in_flight``/``stolen``/``donated``
    counts and the post-tick clock ``t``.

    ``out`` is a :class:`TickOut`: a read-only mapping of those twelve
    keys over one packed int32 device buffer, its only pytree leaf, so
    ``jax.device_get(out)`` is one transfer. ``out[k]`` is a host numpy
    array of the field's own shape and dtype (``fin`` bool), bit-exact
    with what the tick computed; read without ``device_get``, the first
    access fetches the buffer. ``n_arr`` and ``uid_base`` go up as one
    ``(2, n_shards)`` int32 array.

    In lm mode (``learner.feature_kind="lm"``), ``feat`` is an optional
    ``(n_shards, max_arrivals_per_tick, n_features)`` float array of
    injected real-text embeddings and ``labels`` an optional
    ``(n_shards, max_arrivals_per_tick)`` int array of known labels for
    this tick's injections, aligned with the uid order; NaN feature rows
    and -1 labels mean "simulate from the embedding bank". Both must be
    None for Gaussian features.

    The ``serve.dispatch`` span (``repro.obs.timing``) covers argument
    conversion and the call up to its return, which is on dispatch: the
    device work and the fetch of ``out`` come after."""
    cfg = _as_serve_config(cfg)
    with timing.span("serve.dispatch"):
        # a host array: jit's own transfer moves it, in one upload
        inj = np.stack([np.asarray(n_arr, np.int32),
                        np.asarray(uid_base, np.int32)])
        if cfg.learner.feature_kind == "lm":
            S, M = cfg.n_shards, cfg.max_arrivals_per_tick
            F = cfg.learner.n_features
            feat = jnp.full((S, M, F), jnp.nan, jnp.float32) if feat is None \
                else jnp.asarray(feat, jnp.float32)
            labels = jnp.full((S, M), -1, jnp.int32) if labels is None \
                else jnp.asarray(labels, jnp.int32)
            if feat.shape != (S, M, F) or labels.shape != (S, M):
                raise ValueError(
                    f"serve_tick lm injections must be feat ({S}, {M}, {F}) "
                    f"and labels ({S}, {M}); got {feat.shape} / "
                    f"{labels.shape}")
        elif feat is not None or labels is not None:
            raise ValueError(
                "serve_tick feat/labels injections require learner."
                "feature_kind='lm' (Gaussian tasks draw identity in the tick)")
        if cfg.sharding.n_devices > 1:
            return _serve_tick_sharded_jit(cfg)(state, inj, feat, labels)
        return _serve_tick_jit(cfg, state, inj, feat, labels,
                               _bank_for(cfg))
