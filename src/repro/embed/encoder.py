"""Jitted batched embedding extraction through the ``repro.models`` stack.

Token sequences run through ``models.model.forward`` with
``logits_mode="hidden"`` (bf16 compute, f32 final-norm hidden states),
are pooled over the real (unpadded) positions — masked mean or the last
real token — and projected to the learner's feature width by a seeded
Gaussian random projection. Model params, the resolved config and the
projection are all deterministic functions of :class:`EmbedConfig`.

On one device a corpus runs in chunks of at most ``batch_size`` rows,
one jitted call each. A chunk of ``n`` real rows is padded on the host to
:func:`bucket_rows` — the next power of two, at least 8, at most
``batch_size`` — by REPEATING the last row (the ``core.simfast._pad_keys``
idiom: no op mixes the batch axis, so real rows come out the same whatever
the padding, and pad rows are dropped). A few programs cover every chunk,
and a tick's handful of texts runs a program sized to it rather than a
full ``batch_size`` one. With multiple visible devices the micro-batch
axis is pmapped instead (pad to ``batch_size * n_devices`` -> reshape
(D, B, T) -> pmap -> unpad).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.embed.config import EmbedConfig


@functools.lru_cache(maxsize=None)
def resolved_config(ec: EmbedConfig):
    """The (possibly reduced) ModelConfig behind an EmbedConfig."""
    from repro.configs.base import reduced
    from repro.configs.registry import get_config

    cfg = get_config(ec.model)
    return reduced(cfg) if ec.reduced else cfg


@functools.lru_cache(maxsize=None)
def model_params(ec: EmbedConfig):
    """Seeded random-init params for the embedding model (no training —
    random features through a structured architecture are a standard
    strong baseline, and nothing downstream assumes pretrained weights)."""
    from repro.models.model import model_template
    from repro.models.params import init_params

    return init_params(model_template(resolved_config(ec)),
                       jax.random.key(ec.seed))


@functools.lru_cache(maxsize=None)
def projection(ec: EmbedConfig, n_features: int):
    """Seeded Gaussian random projection d_model -> n_features (JL-style;
    variance-preserving 1/sqrt(n_features) scale)."""
    cfg = resolved_config(ec)
    if ec.projection_dim is not None and ec.projection_dim != n_features:
        raise ValueError(
            f"EmbedConfig.projection_dim={ec.projection_dim} != requested "
            f"feature width {n_features} (FeatureSpec.n_features)")
    k = jax.random.fold_in(jax.random.key(ec.seed), 0x9E3779B9)
    return (jax.random.normal(k, (cfg.d_model, n_features))
            / jnp.sqrt(jnp.float32(n_features)))


def _cross_src(cfg, B):
    """Zero stub cross-source for architectures that demand one (whisper's
    encoder frames, VLM image tokens) — task text carries the signal."""
    if cfg.is_encoder_decoder:
        return jnp.zeros((B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    if cfg.n_img_tokens:
        return jnp.zeros((B, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16)
    return None


def _embed_batch(cfg, params, tokens, lengths, pooling, proj):
    """(B, T) int32 tokens + (B,) lengths -> (B, F) f32 features."""
    from repro.models.model import forward

    B, T = tokens.shape
    hidden, _, _ = forward(params, cfg, tokens, mode="train",
                           logits_mode="hidden",
                           cross_src=_cross_src(cfg, B))
    if pooling == "mean":
        mask = (jnp.arange(T)[None, :] < lengths[:, None])
        pooled = ((hidden * mask[:, :, None]).sum(1)
                  / jnp.maximum(lengths, 1).astype(jnp.float32)[:, None])
    else:                                     # "last": final real token
        pooled = hidden[jnp.arange(B), jnp.maximum(lengths - 1, 0)]
    return (pooled @ proj).astype(jnp.float32)


_embed_jit = jax.jit(_embed_batch, static_argnums=(0, 4))
_embed_pmap = jax.pmap(_embed_batch, static_broadcasted_argnums=(0, 4),
                       in_axes=(None, None, 0, 0, None, None))


def bucket_rows(n: int, batch_size: int) -> int:
    """Rows the encoder's program runs for a chunk of ``n`` real rows:
    the next power of two, at least 8 (below that a matmul's rows and the
    recurrence's (8, 128) tiles save nothing), at most ``batch_size``."""
    return min(batch_size, max(8, 1 << (n - 1).bit_length()))


def chunks(n_rows: int, batch_size: int) -> list:
    """``(start, real rows, rows run)`` of each chunk :func:`encode`
    makes of ``n_rows`` rows on one device."""
    return [(i, min(batch_size, n_rows - i),
             bucket_rows(min(batch_size, n_rows - i), batch_size))
            for i in range(0, n_rows, batch_size)]


def encode(ec: EmbedConfig, tokens, lengths, n_features: int, *,
           shard: bool = True):
    """Embed ``(N, seq_len)`` token sequences to ``(N, n_features)`` f32.

    On one device (``shard=False`` or a single visible device) the rows
    run in the :func:`chunks` of ``ec.batch_size``: each chunk padded on
    the host to its :func:`bucket_rows` by repeating its last row, one
    jitted call per chunk, and one fetch for all of them; returns a numpy
    array. With ``shard`` and multiple visible devices each chunk covers
    ``batch_size * n_devices`` rows and pmaps over them. Either way the
    result is independent of chunking and device count."""
    cfg = resolved_config(ec)
    params = model_params(ec)
    proj = projection(ec, n_features)
    tokens = np.asarray(tokens, np.int32)
    lengths = np.asarray(lengths, np.int32)
    if tokens.ndim != 2 or tokens.shape[1] != ec.seq_len:
        raise ValueError(f"tokens must be (N, seq_len={ec.seq_len}), "
                         f"got {tokens.shape}")
    D = jax.local_device_count() if shard else 1
    if D > 1:
        return _encode_pmap(ec, cfg, params, proj, tokens, lengths,
                            n_features, D)
    plan = chunks(int(tokens.shape[0]), ec.batch_size)
    outs = []
    for i, n, rows in plan:
        idx = np.minimum(np.arange(i, i + rows), i + n - 1)
        outs.append(_embed_jit(cfg, params, tokens[idx], lengths[idx],
                               ec.pooling, proj))
    outs = jax.device_get(outs)
    return np.concatenate([o[:n] for o, (_, n, _) in zip(outs, plan)])


def _encode_pmap(ec, cfg, params, proj, tokens, lengths, n_features, D):
    """:func:`encode` over ``D`` devices: chunks of ``batch_size * D``
    rows, padded to full size by repeating the last row."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    N, B = int(tokens.shape[0]), ec.batch_size
    step = B * D
    feats = []
    for i in range(0, N, step):
        tb, lb = tokens[i:i + step], lengths[i:i + step]
        n = int(tb.shape[0])
        pad = step - n
        if pad:
            tb = jnp.concatenate(
                [tb, jnp.broadcast_to(tb[-1:], (pad, tb.shape[1]))])
            lb = jnp.concatenate([lb, jnp.broadcast_to(lb[-1:], (pad,))])
        out = _embed_pmap(cfg, params, tb.reshape(D, B, -1),
                          lb.reshape(D, B), ec.pooling, proj)
        feats.append(out.reshape(step, n_features)[:n])
    return jnp.concatenate(feats, axis=0)
