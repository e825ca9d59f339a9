"""repro.embed: LM-embedding task features end-to-end.

Three layers of guarantees:

  1. **Gaussian bit-identity** — adding the LM path must not move a
     single bit of any ``kind="gaussian"`` scenario's outputs. Pinned
     here as sha256 digests over the stream/serve output bundles of the
     flagship registry scenarios (recorded under jax 0.9.0's default
     threefry RNG; any drift is a regression in the router).
  2. **LM determinism** — an ``lm_stream``/``lm_chance_hard`` run is
     bitwise reproducible under a fixed seed across the stream tick,
     the device-sharded tick and the serve tick.
  3. **Unit semantics** — corpus/encoder/bank behavior, spec lowering,
     field-named config validation, serve-mode injection.
"""
import dataclasses
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import scenarios
from repro.embed import (
    EmbedConfig, EmbeddingBank, bank_gather, embed_texts, embedding_bank,
    encode, make_dataset, make_tokens, resolved_config, signal_strength,
    tokenize_text,
)
from repro.labelstream.router import run_stream, serve_init, serve_tick
from repro.scenarios import get_scenario, override
from repro.scenarios.compile import (
    to_embed_config, to_serve_config, to_stream_config,
)

# a tiny embed config shared by the unit tests (matches the registry's
# _lm_embed so the lru-cached bank/params are reused across the suite)
EC = EmbedConfig(seq_len=16, bank_size=64, batch_size=32)


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
# 1. Gaussian bit-identity (pinned digests of the router's outputs)
# ---------------------------------------------------------------------------

STREAM_KEYS = ("hist", "done", "correct", "sum_tis", "votes_fin",
               "model_known", "backlog_end", "in_flight_end", "dropped",
               "stolen", "donated")

STREAM_DIGESTS = {
    "stream_default": "981f91eb9a23e1d3",
    "chance_hard": "7e2f561b0f41bc3f",
    "skewed_learner_fused": "c8b9ffd2d12e2e26",
    "stream_sharded": "c2675e8bd78690da",
}

SERVE_KEYS = ("fin", "uid", "label", "votes", "conf", "tis", "backlog",
              "in_flight", "stolen", "donated")

SERVE_DIGESTS = {
    "serve_default": "0d510aaeb2ea3238",
    "stream_sharded": "a8a899f799d20e4d",
}

# long enough that every pinned scenario finalizes tasks (at 0.01
# arrivals/s and 5 s ticks, 40 ticks can see no arrival at all)
HORIZON = 160


@pytest.mark.parametrize("name", sorted(STREAM_DIGESTS))
def test_gaussian_stream_outputs_bit_identical_to_pre_embed(name):
    res = run_stream(to_stream_config(get_scenario(name)), HORIZON,
                     n_reps=2, seed=0)
    got = _digest(res[k] for k in STREAM_KEYS)
    assert got == STREAM_DIGESTS[name], (
        f"{name}: gaussian stream outputs drifted from the pre-embed "
        f"pin ({got} != {STREAM_DIGESTS[name]}) — the LM feature path "
        "must be a no-op for kind='gaussian'")


@pytest.mark.parametrize("name,ov", [("serve_default", None),
                                     ("stream_sharded", {"window": 8})])
def test_gaussian_serve_outputs_bit_identical_to_pre_embed(name, ov):
    spec = override(get_scenario(name), ov) if ov else get_scenario(name)
    cfg = to_serve_config(spec)
    st = serve_init(cfg, seed=0)
    S = cfg.n_shards
    chunks, base = [], np.zeros((S,), np.int64)
    for i in range(8):
        n = np.asarray([(i + s) % 3 for s in range(S)], np.int32)
        st, o = serve_tick(cfg, st, n, base.astype(np.int32))
        base += n
        chunks.extend(np.asarray(o[k]) for k in SERVE_KEYS)
    got = _digest(chunks)
    assert got == SERVE_DIGESTS[name]


# ---------------------------------------------------------------------------
# 2. LM determinism across all three tick paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["lm_stream", "lm_chance_hard"])
def test_lm_stream_bitwise_deterministic(name):
    cfg = to_stream_config(get_scenario(name))
    a = run_stream(cfg, HORIZON, n_reps=2, seed=0)
    b = run_stream(cfg, HORIZON, n_reps=2, seed=0)
    assert _digest(a[k] for k in STREAM_KEYS) == \
        _digest(b[k] for k in STREAM_KEYS)
    # and the run did something: tasks arrived and finalized
    assert int(np.asarray(a["done"]).sum()) > 0


def test_lm_sharded_stream_deterministic_and_runs():
    cfg = to_stream_config(get_scenario(
        "lm_stream", {"sharding.n_devices": 1}))
    a = run_stream(cfg, 40, n_reps=2, seed=0)
    b = run_stream(cfg, 40, n_reps=2, seed=0)
    assert _digest(a[k] for k in STREAM_KEYS) == \
        _digest(b[k] for k in STREAM_KEYS)


def test_lm_serve_tick_deterministic():
    cfg = to_serve_config(get_scenario("lm_stream"))
    outs = []
    for _rep in range(2):
        st = serve_init(cfg, seed=0)
        chunks, base = [], np.zeros((cfg.n_shards,), np.int64)
        for i in range(6):
            n = np.asarray([(i + s) % 2 for s in range(cfg.n_shards)],
                           np.int32)
            st, o = serve_tick(cfg, st, n, base.astype(np.int32))
            base += n
            chunks.extend(np.asarray(o[k]) for k in SERVE_KEYS)
        outs.append(_digest(chunks))
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# 3a. corpus
# ---------------------------------------------------------------------------

def test_make_tokens_deterministic_and_class_correlated():
    cfg = resolved_config(EC)
    labels = np.array([0, 0, 1, 1], np.int32)
    hard = np.array([False, False, False, False])
    t1, l1 = make_tokens(EC, labels, hard, 2, cfg.vocab_size, 3.0)
    t2, l2 = make_tokens(EC, labels, hard, 2, cfg.vocab_size, 3.0)
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(l1, l2)
    assert t1.shape == (4, EC.seq_len) and t1.dtype == np.int32
    assert (l1 >= 1).all() and (l1 <= EC.seq_len).all()
    assert (t1 >= 0).all() and (t1 < cfg.vocab_size).all()


def test_hard_tasks_carry_weaker_signal():
    # signal strength shrinks for hard tasks when hard_sep_scale < 1
    easy = signal_strength(3.0, hard_sep_scale=0.1, hard=False)
    hard = signal_strength(3.0, hard_sep_scale=0.1, hard=True)
    assert hard < easy


def test_tokenize_text_deterministic_and_bounded():
    a, la = tokenize_text("label this movie review", 16, 256)
    b, lb = tokenize_text("label this movie review", 16, 256)
    c, _ = tokenize_text("a completely different task", 16, 256)
    np.testing.assert_array_equal(a, b)
    assert la == lb and 1 <= la <= 16
    assert a.shape == (16,) and a.dtype == np.int32
    assert not np.array_equal(a, c)


# ---------------------------------------------------------------------------
# 3b. encoder
# ---------------------------------------------------------------------------

def test_encode_shapes_padding_invariance_and_determinism():
    cfg = resolved_config(EC)
    rng = np.random.default_rng(0)
    N = 5   # deliberately not a multiple of batch_size: pad path
    tokens = rng.integers(0, cfg.vocab_size, (N, EC.seq_len)).astype(np.int32)
    lengths = rng.integers(4, EC.seq_len + 1, N).astype(np.int32)
    e1 = np.asarray(encode(EC, tokens, lengths, 8, shard=False))
    e2 = np.asarray(encode(EC, tokens, lengths, 8, shard=False))
    assert e1.shape == (N, 8) and e1.dtype == np.float32
    np.testing.assert_array_equal(e1, e2)
    assert np.isfinite(e1).all()
    # masked pooling: tokens past `length` must not affect the embedding
    tokens2 = tokens.copy()
    tokens2[0, int(lengths[0]):] = (tokens2[0, int(lengths[0]):] + 7) \
        % cfg.vocab_size
    e3 = np.asarray(encode(EC, tokens2, lengths, 8, shard=False))
    np.testing.assert_array_equal(e1[0], e3[0])


def test_encode_last_pooling_differs_from_mean():
    cfg = resolved_config(EC)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab_size, (3, EC.seq_len)).astype(np.int32)
    lengths = np.full((3,), EC.seq_len, np.int32)
    em = np.asarray(encode(EC, tokens, lengths, 8, shard=False))
    el = np.asarray(encode(dataclasses.replace(EC, pooling="last"),
                           tokens, lengths, 8, shard=False))
    assert not np.array_equal(em, el)


@pytest.mark.parametrize("N", [1, 5, 8, 9, 33, 64, 65, 100])
def test_encode_bucketed_chunks_match_full_chunks(N, monkeypatch):
    """Each chunk runs in a program of its bucket's rows (a power of two
    in [8, batch_size], at least its real rows), one encoder call per
    chunk, and its rows match the same texts run in full chunks."""
    import repro.embed.encoder as encoder

    cfg = resolved_config(EC)
    B = EC.batch_size
    rng = np.random.default_rng(N)
    full = -(-N // B) * B
    tokens = rng.integers(0, cfg.vocab_size, (full, EC.seq_len)).astype(
        np.int32)
    lengths = rng.integers(1, EC.seq_len + 1, full).astype(np.int32)
    ref = np.asarray(encode(EC, tokens, lengths, 8, shard=False))[:N]
    plan = encoder.chunks(N, B)
    for _, n, rows in plan:
        assert rows >= n and 8 <= rows <= B and rows & (rows - 1) == 0
    real_jit = encoder._embed_jit
    shapes = []

    def counted(cfg_, params, tb, lb, pooling, proj):
        shapes.append(tb.shape[0])
        return real_jit(cfg_, params, tb, lb, pooling, proj)

    monkeypatch.setattr(encoder, "_embed_jit", counted)
    got = encode(EC, tokens[:N], lengths[:N], 8, shard=False)
    assert got.shape == (N, 8) and got.dtype == np.float32
    assert shapes == [rows for _, _, rows in plan]
    assert len(shapes) == -(-N // B)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=0)


def test_hidden_logits_mode_returns_final_norm_states():
    from repro.embed.encoder import model_params
    from repro.models.model import forward

    cfg = resolved_config(EC)
    params = model_params(EC)
    toks = jnp.zeros((2, 8), jnp.int32)
    h, _, _ = forward(params, cfg, toks, logits_mode="hidden")
    assert h.shape == (2, 8, cfg.d_model)
    assert h.dtype == jnp.float32


# ---------------------------------------------------------------------------
# 3c. bank
# ---------------------------------------------------------------------------

def test_embedding_bank_layout_and_cache():
    b1 = embedding_bank(EC, 2, 8, 3.0, 0.1)
    b2 = embedding_bank(EC, 2, 8, 3.0, 0.1)
    assert b1 is b2                          # lru-cached: built once
    assert isinstance(b1, EmbeddingBank)
    assert b1.feats.shape == (2, 2, EC.bank_size // 4, 8)
    assert b1.n_classes == 2 and b1.n_features == 8
    feats = np.asarray(b1.feats)
    assert np.isfinite(feats).all()
    # standardized over the bank: global per-feature mean ~0, std ~1
    flat = feats.reshape(-1, 8)
    np.testing.assert_allclose(flat.mean(0), 0.0, atol=1e-4)
    np.testing.assert_allclose(flat.std(0), 1.0, atol=1e-3)
    # the class structure survives encoding: class means differ
    cm = feats.mean(axis=(0, 2))             # (C, F)
    assert np.linalg.norm(cm[0] - cm[1]) > 0.1


def test_bank_size_layout_validated():
    with pytest.raises(ValueError, match="bank_size"):
        embedding_bank(dataclasses.replace(EC, bank_size=6), 4, 8, 3.0)


def test_bank_gather_indexing():
    b = embedding_bank(EC, 2, 8, 3.0, 0.1)
    K = b.n_variants
    u = jnp.asarray([0.0, 0.999, 0.5])
    tl = jnp.asarray([0, 1, 5], jnp.int32)   # 5 clips to C-1
    diff = jnp.asarray([1.0, 0.5, 1.0])      # diff<1 -> hard half
    g = np.asarray(bank_gather(b.feats, u, tl, diff))
    np.testing.assert_array_equal(g[0], np.asarray(b.feats)[0, 0, 0])
    np.testing.assert_array_equal(g[1], np.asarray(b.feats)[1, 1, K - 1])
    np.testing.assert_array_equal(g[2], np.asarray(b.feats)[0, 1, K // 2])


def test_make_dataset_deterministic_and_learnable():
    spec = get_scenario("lm_stream")
    X, y, Xt, yt = make_dataset(spec, 64, 32, seed=0)
    X2, y2, _, _ = make_dataset(spec, 64, 32, seed=0)
    np.testing.assert_array_equal(X, X2)
    np.testing.assert_array_equal(y, y2)
    assert X.shape == (64, spec.features.n_features)
    assert Xt.shape == (32, spec.features.n_features)
    # a different seed gives a different corpus
    X3, _, _, _ = make_dataset(spec, 64, 32, seed=1)
    assert not np.array_equal(X, X3)
    # a ridge probe on the embeddings beats chance comfortably: the
    # class structure of the TEXT survives encoder + projection
    X, y, Xt, yt = make_dataset(spec, 256, 64, seed=1)
    Y = np.eye(spec.n_classes)[y]
    W = np.linalg.solve(X.T @ X + 0.1 * np.eye(X.shape[1]), X.T @ Y)
    assert ((Xt @ W).argmax(1) == yt).mean() > 0.8


def test_embed_texts_lands_in_bank_space():
    v = np.asarray(embed_texts(EC, ["classify this", "another task"],
                               2, 8, 3.0, 0.1))
    assert v.shape == (2, 8)
    assert np.isfinite(v).all()
    # deterministic
    v2 = np.asarray(embed_texts(EC, ["classify this", "another task"],
                                2, 8, 3.0, 0.1))
    np.testing.assert_array_equal(v, v2)


# ---------------------------------------------------------------------------
# 3d. spec surface + lowering
# ---------------------------------------------------------------------------

def test_to_embed_config_lowers_embedspec_fields():
    spec = get_scenario("lm_stream")
    ec = to_embed_config(spec)
    assert isinstance(ec, EmbedConfig)
    for f in dataclasses.fields(EmbedConfig):
        assert getattr(ec, f.name) == getattr(spec.embed, f.name)


def test_stream_lowering_threads_feature_kind():
    lm = to_stream_config(get_scenario("lm_stream"))
    assert lm.learner.feature_kind == "lm"
    assert isinstance(lm.learner.embed, EmbedConfig)
    ga = to_stream_config(get_scenario("stream_default"))
    assert ga.learner.feature_kind == "gaussian"
    assert ga.learner.embed is None


def test_batch_engines_reject_lm_features():
    # batch arrivals + lm features is a valid SPEC (run_learning builds
    # the dataset itself), but the batch engines consume matrices — the
    # compiler must say so by field name
    spec = scenarios.ScenarioSpec(
        features=scenarios.FeatureSpec(kind="lm"),
        embed=scenarios.EmbedSpec(bank_size=64))
    from repro.scenarios.compile import to_fast_config
    with pytest.raises(ValueError, match="features.kind"):
        to_fast_config(spec)


def test_run_learning_builds_lm_dataset():
    spec = scenarios.ScenarioSpec(
        n_tasks=20,
        features=scenarios.FeatureSpec(kind="lm", n_features=8,
                                       class_sep=3.0),
        embed=scenarios.EmbedSpec(seq_len=16, bank_size=64,
                                  batch_size=32))
    res = scenarios.run_learning(spec, engine="simfast", seed=0,
                                 rounds=2, n_reps=2, n_train=48,
                                 n_test=24)
    acc = np.asarray(res["curve"]["acc"])
    assert np.isfinite(acc).all()


def test_run_learning_rejects_partial_dataset():
    spec = get_scenario("lm_stream")
    y = np.zeros((8,), np.int32)
    with pytest.raises(ValueError, match="X"):
        scenarios.run_learning(spec, None, y, None, None)


# ---------------------------------------------------------------------------
# 3e. validation: field-named errors for kind="lm" cross-field rules
# ---------------------------------------------------------------------------

def test_spec_lm_requires_learner_on_stream():
    with pytest.raises(ValueError, match="features.kind"):
        scenarios.ScenarioSpec(
            arrivals=scenarios.ArrivalSpec(kind="poisson", rate=0.01),
            features=scenarios.FeatureSpec(kind="lm"),
            embed=scenarios.EmbedSpec(bank_size=64))


def test_spec_lm_projection_dim_must_match_n_features():
    with pytest.raises(ValueError, match="embed.projection_dim"):
        scenarios.ScenarioSpec(
            features=scenarios.FeatureSpec(kind="lm", n_features=8),
            embed=scenarios.EmbedSpec(bank_size=64, projection_dim=16))


def test_spec_lm_bank_size_multiple_of_2c():
    with pytest.raises(ValueError, match="embed.bank_size"):
        scenarios.ScenarioSpec(
            n_classes=3,
            features=scenarios.FeatureSpec(kind="lm"),
            embed=scenarios.EmbedSpec(bank_size=64))


def test_spec_lm_bank_must_cover_window():
    with pytest.raises(ValueError, match="embed.bank_size"):
        scenarios.ScenarioSpec(
            window=64, backlog=1024,
            arrivals=scenarios.ArrivalSpec(kind="poisson", rate=0.01),
            pool=scenarios.PoolSpec(pool_size=8, n_shards=2),
            features=scenarios.FeatureSpec(kind="lm"),
            embed=scenarios.EmbedSpec(bank_size=8),
            policy=scenarios.PolicySpec(
                learner=scenarios.LearnerSpec(enabled=True)))


def test_feature_kind_validated():
    with pytest.raises(ValueError, match="FeatureSpec.kind"):
        scenarios.FeatureSpec(kind="bert")
    with pytest.raises(ValueError, match="EmbedSpec.pooling"):
        scenarios.EmbedSpec(pooling="max")
    with pytest.raises(ValueError, match="EmbedConfig.pooling"):
        EmbedConfig(pooling="max")


def test_stream_config_validation_field_named():
    from repro.labelstream.router import (
        StreamConfig, StreamLearnerConfig, _validate_stream_config,
    )
    with pytest.raises(ValueError, match="feature_kind"):
        _validate_stream_config(StreamConfig(
            learner=StreamLearnerConfig(feature_kind="bert")))
    # lm without an embed config
    with pytest.raises(ValueError, match="embed"):
        _validate_stream_config(StreamConfig(
            learner=StreamLearnerConfig(enabled=True, feature_kind="lm")))
    # embed set on a gaussian config
    with pytest.raises(ValueError, match="embed"):
        _validate_stream_config(StreamConfig(
            learner=StreamLearnerConfig(enabled=True,
                                        feature_kind="gaussian",
                                        embed=EC)))


# ---------------------------------------------------------------------------
# 3f. serve-mode injection
# ---------------------------------------------------------------------------

def test_serve_lm_accepts_injected_features_and_labels():
    cfg = to_serve_config(get_scenario("lm_stream"))
    S, M, F = cfg.n_shards, cfg.max_arrivals_per_tick, \
        cfg.learner.n_features
    st = serve_init(cfg, seed=0)
    feat = np.full((S, M, F), np.nan, np.float32)
    labels = np.full((S, M), -1, np.int32)
    feat[0, 0] = 0.25
    labels[0, 0] = 1
    n = np.zeros((S,), np.int32)
    n[0] = 1
    st, o = serve_tick(cfg, st, n, np.zeros((S,), np.int32),
                       feat=feat, labels=labels)
    assert np.asarray(o["backlog"]).sum() + np.asarray(
        o["in_flight"]).sum() + np.asarray(o["fin"]).sum() > 0


def test_serve_gaussian_rejects_injection():
    cfg = to_serve_config(get_scenario("serve_default"))
    S, M = cfg.n_shards, cfg.max_arrivals_per_tick
    st = serve_init(cfg, seed=0)
    feat = np.zeros((S, M, cfg.learner.n_features), np.float32)
    with pytest.raises(ValueError, match="lm"):
        serve_tick(cfg, st, np.zeros((S,), np.int32),
                   np.zeros((S,), np.int32), feat=feat)
