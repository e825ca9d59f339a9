"""LM-embedding feature path (repro.embed): encoder throughput and the
learner on real representations.

Sections (BENCH_embed.json):

  1. encoder throughput — embeddings/sec through the jitted padded/masked
     batched encoder (``logits_mode="hidden"`` forward -> pooling ->
     random projection), with the compile-vs-warm split from
     ``repro.obs.timing``. Wall-clock rates are info-only (machine-
     dependent); the committed gate is downstream accuracy.
  2. bank build — wall-clock to materialize the device-resident
     ``EmbeddingBank`` (corpus -> encoder -> standardize), info-only,
     plus a gather sanity row (bank reuse across runs is what keeps the
     jitted tick free of LM forwards).
  3. chance_hard recovery — the headline: difficulty-aware admission
     (``uncertain_learnable``) under sustained overload on the
     chance-level-hard-tasks workload, Gaussian features
     (``chance_hard``) vs LM embeddings of the same crowd/difficulty
     process (``lm_chance_hard``). Hard tasks' class-signal token rate
     is shrunk, so their embeddings collapse toward the background-text
     manifold; the learnability head must find that structure in REAL
     representations and steer admission toward resolvable tasks (the
     FIFO mix on this workload scores ~0.80 — the ceiling both feature
     paths climb toward). Gated: the LM row's admission accuracy and
     its throughput ratio vs the Gaussian row (matched-throughput
     comparison, both machine-independent simulated quantities) at
     FIXED horizon/reps in smoke and full — the committed baseline gates
     this exact measurement.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, timed, write_bench_json

#: fixed dims for the gated recovery comparison (same in smoke and full)
RECOVERY_DIMS = dict(horizon=600, reps=2, seed=2, rate_scale=2.5)


def _encoder_throughput(bench, smoke):
    from repro.embed import EmbedConfig, encode, make_tokens, resolved_config
    from repro.obs import timing

    ec = EmbedConfig(seq_len=16, bank_size=64,
                     batch_size=32 if smoke else 64)
    cfg = resolved_config(ec)
    N, C = (256, 4) if smoke else (2048, 4)
    rng = np.random.default_rng(0)
    labels = rng.integers(0, C, N).astype(np.int32)
    hard = rng.random(N) < 0.3
    tokens, lengths = make_tokens(ec, labels, hard, C, cfg.vocab_size, 2.0)
    run = lambda: np.asarray(encode(ec, tokens, lengths, 16, shard=False))
    timing.timeit("bench.embed_encode", run)  # cold: trace + XLA compile
    timing.timeit("bench.embed_encode", run)  # warm: execute only
    row = [r for r in timing.summary()
           if r["name"] == "bench.embed_encode"][0]
    cold_s = row["cold_s"]
    warm_s = row["warm_s"] or cold_s
    emit("embed_encode", 1e6 * warm_s / N,
         f"n={N};seq_len={ec.seq_len};cold_s={cold_s:.2f};"
         f"warm_s={warm_s:.3f};"
         f"cold_eps={N / cold_s:.0f};warm_eps={N / warm_s:.0f}")
    bench.update({
        # wall-clock rates: info-only, runner-dependent
        "encode_cold_embeddings_per_s": N / cold_s,
        "encode_warm_embeddings_per_s": N / warm_s,
    })


def _bank_build(bench, smoke):
    from repro import scenarios
    from repro.embed.bank import bank_gather, embedding_bank
    from repro.scenarios.compile import to_embed_config

    spec = scenarios.get_scenario("lm_chance_hard")
    ec = to_embed_config(spec)
    embedding_bank.cache_clear()            # measure a true cold build
    bank, us = timed(lambda: embedding_bank(
        ec, spec.n_classes, spec.features.n_features,
        spec.features.class_sep, spec.features.hard_sep_scale),
        name="embed.bank_build")
    # gather sanity: one uniform draw must address every (hard, class)
    # cell and return finite standardized vectors
    u = np.linspace(0.0, 0.999, 16, dtype=np.float32)
    tl = np.arange(16, dtype=np.int32) % bank.n_classes
    g = np.asarray(bank_gather(bank.feats, u, tl,
                               np.where(np.arange(16) % 2 == 0, 1.0, 0.5)
                               .astype(np.float32)))
    assert np.isfinite(g).all() and g.shape == (16, bank.n_features)
    emit("embed_bank_build", us,
         f"bank_size={ec.bank_size};n_features={bank.n_features};"
         f"build_s={us / 1e6:.2f};gather_ok=1")
    bench["bank_build_s"] = us / 1e6        # info-only


def _chancehard_recovery(bench, smoke):
    """Section 3: LM vs Gaussian features under difficulty-aware
    admission at sustained overload — fixed dims, gated."""
    from repro import scenarios

    d = RECOVERY_DIMS
    rows = {}
    for name, scen in (("gaussian", "chance_hard"), ("lm", "lm_chance_hard")):
        spec = scenarios.get_scenario(
            scen, {"policy.admission.kind": "uncertain_learnable"})
        s = scenarios.run(spec, engine="stream", horizon=d["horizon"],
                          n_reps=d["reps"], seed=d["seed"],
                          rate_scale=d["rate_scale"])["metrics"]
        rows[name] = s
        emit(f"embed_admit_{name}_chancehard", 0.0,
             f"sustained_tps={s['sustained_rate']:.4f};"
             f"p95_s={s['p95_tis']:.0f};acc={s['accuracy']:.3f};"
             f"votes_per_task={s['votes_per_task']:.2f};"
             f"model_known_frac={s['model_known_frac']:.2f}")
    delta_pp = 100 * (rows["lm"]["accuracy"] - rows["gaussian"]["accuracy"])
    tps_ratio = rows["lm"]["sustained_rate"] \
        / max(rows["gaussian"]["sustained_rate"], 1e-9)
    emit("embed_chancehard_recovery", 0.0,
         f"acc_gaussian={rows['gaussian']['accuracy']:.3f};"
         f"acc_lm={rows['lm']['accuracy']:.3f};"
         f"delta_pp={delta_pp:.1f};tps_ratio={tps_ratio:.2f};"
         f"overload_x={d['rate_scale']};"
         "target=lm_recovers_accuracy_at_matched_tps_toward_fifo_0.80")
    bench.update({
        "lm_chancehard_accuracy": (rows["lm"]["accuracy"], "higher"),
        "lm_vs_gaussian_acc_delta_pp": (delta_pp, "higher"),
        "lm_vs_gaussian_tps_ratio": (tps_ratio, "higher"),
        "gaussian_chancehard_accuracy": rows["gaussian"]["accuracy"],
        "lm_chancehard_tps": rows["lm"]["sustained_rate"],
        "lm_votes_per_task": rows["lm"]["votes_per_task"],
    })


def run(smoke: bool = False):
    bench = {}
    _encoder_throughput(bench, smoke)
    _bank_build(bench, smoke)
    _chancehard_recovery(bench, smoke)
    write_bench_json("embed", bench,
                     meta=dict(smoke=smoke, **RECOVERY_DIMS))
