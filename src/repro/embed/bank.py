"""Precomputed device-resident embedding bank for the jitted engines.

The streaming tick cannot run an LM forward per arrival, and it must not
consume EXTRA randomness (the Gaussian path's uniform streams are pinned
bit-for-bit by tests). So the LM feature path is a GATHER: a bank of
``bank_size`` task embeddings laid out ``(2, n_classes, variants,
n_features)`` — axis 0 easy/hard — is built once per config on the host
(corpus -> encoder -> standardize), cached, and handed to the compiled
tick, which indexes it with the SAME uniform draw the Gaussian path
would have spent on its first feature coordinate. Identical workload
randomness, LM features.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.embed.config import EmbedConfig
from repro.embed.corpus import make_tokens
from repro.embed.encoder import encode, resolved_config
from repro.learning.features import standardize
from repro.obs import timing


class EmbeddingBank(NamedTuple):
    """``feats[h, c, v]`` is variant ``v`` of an easy (``h=0``) or hard
    (``h=1``) task of class ``c`` — f32, standardized over the bank.
    ``mean``/``std`` are the pre-standardization bank statistics, kept so
    live text embeddings (:func:`embed_texts`) land in the same feature
    space as the gathered rows."""
    feats: jax.Array                      # (2, C, K, F)
    mean: jax.Array                       # (F,)
    std: jax.Array                        # (F,)

    @property
    def n_classes(self) -> int:
        return self.feats.shape[1]

    @property
    def n_variants(self) -> int:
        return self.feats.shape[2]

    @property
    def n_features(self) -> int:
        return self.feats.shape[3]


@functools.lru_cache(maxsize=None)
def embedding_bank(ec: EmbedConfig, n_classes: int, n_features: int,
                   class_sep: float,
                   hard_sep_scale: float = 1.0) -> EmbeddingBank:
    """Build (and cache) the bank for one embedding + workload config."""
    C = n_classes
    if ec.bank_size % (2 * C) != 0 or ec.bank_size < 2 * C:
        raise ValueError(
            f"EmbedConfig.bank_size={ec.bank_size} must be a positive "
            f"multiple of 2 * n_classes = {2 * C} (easy/hard x class x "
            "variant layout)")
    K = ec.bank_size // (2 * C)
    # row order (h, c, v): reshape below restores the (2, C, K, F) layout
    hard = np.repeat(np.arange(2), C * K).astype(bool)
    labels = np.tile(np.repeat(np.arange(C, dtype=np.int32), K), 2)
    cfg = resolved_config(ec)
    tokens, lengths = make_tokens(ec, labels, hard, C, cfg.vocab_size,
                                  class_sep, hard_sep_scale)
    E = jnp.asarray(encode(ec, tokens, lengths, n_features, shard=False))
    mu = E.mean(axis=0)
    sd = E.std(axis=0)
    X = standardize(E)
    return EmbeddingBank(feats=X.reshape(2, C, K, n_features),
                         mean=mu, std=sd)


@functools.lru_cache(maxsize=None)
def _bank_stats(ec: EmbedConfig, n_classes: int, n_features: int,
                class_sep: float, hard_sep_scale: float):
    """The bank's mean and floored std, fetched once, for standardizing
    live embeddings on the host."""
    bank = embedding_bank(ec, n_classes, n_features, class_sep,
                          hard_sep_scale)
    mean, std = jax.device_get((bank.mean, bank.std))
    return mean, np.maximum(std, np.float32(1e-6))


def bank_gather(feats, u, tl, diff):
    """Jit-safe bank lookup: one uniform ``u`` in [0, 1) picks the
    variant, ``tl`` the class row, ``diff < 1`` the hard half — the
    in-tick replacement for the Gaussian ``_task_features`` draw."""
    K = feats.shape[2]
    v = jnp.minimum((u * K).astype(jnp.int32), K - 1)
    h = (diff < 1.0).astype(jnp.int32)
    return feats[h, jnp.clip(tl, 0, feats.shape[1] - 1), v]


def embed_texts(ec: EmbedConfig, texts, n_classes: int, n_features: int,
                class_sep: float, hard_sep_scale: float = 1.0):
    """Encode real submitted text into the bank's feature space.

    The serving loop's embed-then-inject path: hash-tokenize each string
    (:func:`repro.embed.corpus.tokenize_text`), run the batched encoder,
    then normalize with the BANK's pre-standardization statistics — not
    the batch's own — so one-off live submissions land on the same scale
    as the precomputed rows the learner was trained on (in numpy, from
    the bank's statistics fetched once). Returns an ``(N, n_features)``
    f32 numpy array.

    Spans (``repro.obs.timing``): ``embed.tokenize``, and ``embed.encode``
    up to the encoder's return, which fetches its features."""
    from repro.embed.corpus import tokenize_text

    mean, std = _bank_stats(ec, n_classes, n_features, class_sep,
                            hard_sep_scale)
    cfg = resolved_config(ec)
    with timing.span("embed.tokenize"):
        pairs = [tokenize_text(t, ec.seq_len, cfg.vocab_size) for t in texts]
        tokens = np.stack([p[0] for p in pairs])
        lengths = np.asarray([p[1] for p in pairs], np.int32)
    with timing.span("embed.encode"):
        E = encode(ec, tokens, lengths, n_features, shard=False)
    return (E - mean) / std


def make_dataset(spec, n_train: int, n_test: int, seed: int = 0):
    """Host-side LM-feature dataset for the BATCH learning loops
    (``scenarios.run_learning`` / the example): fresh labels and
    difficulty flags from ``seed``, a fresh corpus (the dataset seed
    folds into the embed seed so datasets never alias the bank), encoded
    and standardized. Returns ``(X, y, X_test, y_test)`` numpy arrays."""
    from repro.scenarios.compile import to_embed_config

    ec = to_embed_config(spec)
    C, feat, diff = spec.n_classes, spec.features, spec.difficulty
    rng = np.random.default_rng(seed)
    N = n_train + n_test
    labels = rng.integers(0, C, N).astype(np.int32)
    hard = rng.random(N) < diff.p_hard
    ec = dataclasses.replace(ec, seed=ec.seed + 7919 * (seed + 1))
    cfg = resolved_config(ec)
    tokens, lengths = make_tokens(ec, labels, hard, C, cfg.vocab_size,
                                  feat.class_sep, feat.hard_sep_scale)
    X = np.asarray(standardize(
        encode(ec, tokens, lengths, feat.n_features)))
    return (X[:n_train], labels[:n_train],
            X[n_train:], labels[n_train:])
