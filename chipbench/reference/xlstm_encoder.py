"""Plain float32 reference of the text encoder: xLSTM (arXiv:2405.04517)
as the labeling service configures it, from text to task features.

- Tokens: each whitespace word, up to ``seq_len`` of them, is the first
  four bytes of its SHA-1 digest (big-endian) modulo the vocabulary;
  positions past the text are 0, and an empty text is one 0 token.
- Weights, drawn from the seed in this order with ``jax.random.split``
  (one key per tensor): the token embedding (normal x 0.02), the final
  norm's scale (ones), then each stack of the ``n_layers / 2`` layer pairs:
  the mLSTM block's ``b_if`` (zeros), ``hnorm.scale``, ``norm.scale``
  (ones), ``w_down``, ``w_if``, ``w_k``, ``w_q``, ``w_up``, ``w_z``, and the
  sLSTM block's ``b_gates`` (zeros), ``gnorm.scale``, ``norm.scale``
  (ones), ``r_gates``, ``w_down``, ``w_gates``, ``w_up``; every matrix is
  normal / sqrt(fan-in), the fan-in being its second-to-last dimension.
- Layers: the pair (mLSTM, sLSTM) repeats, each block pre-normed (RMS
  norm) inside a residual. mLSTM: up-projection ``u`` and sigmoid gate
  ``z`` from the normed input; per head, queries and keys from ``u``
  (keys scaled by ``head_dim**-0.5``), values the head's slice of ``u``;
  exponential input gate and log-sigmoid forget gate; the stabilized
  matrix-memory recurrence; the output RMS-normed, gated by ``z`` and
  projected down. sLSTM: gate pre-activations from the normed input plus
  a per-head recurrent term; exponential input gate, log-sigmoid forget
  gate, stabilized scalar memory; output RMS-normed and passed through a
  GeLU-gated (tanh form) feed-forward of width ``ff_inner``.
- Features: the final-norm hidden states, averaged over the text's real
  positions, times a Gaussian projection to ``n_features`` drawn from the
  seed folded with ``0x9E3779B9`` and scaled by ``1/sqrt(n_features)``.

The recurrences run step by step (the sequential form), every matrix
product at ``Precision.HIGHEST``. ``dtype="float8_e4m3fn"`` rounds each
matrix product's inputs to that type: the control.
"""
from __future__ import annotations

import functools
import hashlib

import numpy as np

PROJ_FOLD = 0x9E3779B9


def tokenize(text: str, seq_len: int, vocab: int):
    words = text.split()[:seq_len]
    out = np.zeros((seq_len,), np.int32)
    if not words:
        return out, 1
    for i, w in enumerate(words):
        out[i] = int.from_bytes(hashlib.sha1(
            w.encode("utf-8", "replace")).digest()[:4], "big") % vocab
    return out, len(words)


def _shapes(m: dict):
    d, H, dqk, V = m["d_model"], m["n_heads"], m["head_dim"], m["vocab_size"]
    G, fi, di, dh = m["n_layers"] // 2, m["ff_inner"], 2 * m["d_model"], \
        m["d_model"] // m["n_heads"]
    return [
        ("embed", (V, d), "embed"),
        ("final_norm", (d,), "ones"),
        ("m.b_if", (G, 2 * H), "zeros"), ("m.hnorm", (G, di), "ones"),
        ("m.norm", (G, d), "ones"), ("m.w_down", (G, di, d), "fan_in"),
        ("m.w_if", (G, d, 2 * H), "fan_in"),
        ("m.w_k", (G, di, H * dqk), "fan_in"),
        ("m.w_q", (G, di, H * dqk), "fan_in"),
        ("m.w_up", (G, d, di), "fan_in"), ("m.w_z", (G, d, di), "fan_in"),
        ("s.b_gates", (G, 4 * d), "zeros"), ("s.gnorm", (G, d), "ones"),
        ("s.norm", (G, d), "ones"), ("s.r_gates", (G, H, dh, 4 * dh), "fan_in"),
        ("s.w_down", (G, fi, d), "fan_in"), ("s.w_gates", (G, d, 4 * d), "fan_in"),
        ("s.w_up", (G, d, 2 * fi), "fan_in"),
    ]


def weights(m: dict, seed: int) -> dict:
    """Every tensor of the encoder, float32, drawn from ``seed``."""
    import jax
    import jax.numpy as jnp
    shapes = _shapes(m)
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    out = {}
    for (name, shape, init), k in zip(shapes, keys):
        if init == "zeros":
            out[name] = jnp.zeros(shape, jnp.float32)
        elif init == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        elif init == "embed":
            out[name] = jax.random.normal(k, shape, jnp.float32) * 0.02
        else:
            out[name] = jax.random.normal(k, shape, jnp.float32) \
                * np.float32(1.0 / np.sqrt(shape[-2]))
    pk = jax.random.fold_in(jax.random.key(seed), PROJ_FOLD)
    out["proj"] = jax.random.normal(pk, (m["d_model"], m["n_features"])) \
        / jnp.sqrt(jnp.float32(m["n_features"]))
    return out


def _forward(m: dict, dtype: str, w: dict, tokens, lengths):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    d, H, dqk, eps = m["d_model"], m["n_heads"], m["head_dim"], m["norm_eps"]
    di, dh = 2 * d, d // H
    dv = di // H
    B, S = tokens.shape

    def q8(x):
        if dtype == "float32":
            return x
        return x.astype(getattr(jnp, dtype)).astype(jnp.float32)

    def mm(a, b):
        return jnp.matmul(q8(a), q8(b), precision=hi)

    def rms(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * scale

    def gelu(x):
        return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                         * (x + 0.044715 * x ** 3)))

    def mlstm(x, p):
        h = rms(x, p["m.norm"])
        u = mm(h, p["m.w_up"])
        z = jax.nn.sigmoid(mm(h, p["m.w_z"]))
        q = mm(u, p["m.w_q"]).reshape(B, S, H, dqk)
        k = mm(u, p["m.w_k"]).reshape(B, S, H, dqk) * dqk ** -0.5
        v = u.reshape(B, S, H, dv)
        gf = (mm(h, p["m.w_if"]) + p["m.b_if"]).reshape(B, S, H, 2)
        log_i, log_f = gf[..., 0], jax.nn.log_sigmoid(gf[..., 1])

        def step(c, xs):
            C, n, mx = c
            qt, kt, vt, li, lf = xs
            m_new = jnp.maximum(lf + mx, li)
            fp = jnp.exp(lf + mx - m_new)
            ip = jnp.exp(li - m_new)
            C = fp[..., None, None] * C \
                + (ip[..., None] * kt)[..., None] * vt[..., None, :]
            n = fp[..., None] * n + ip[..., None] * kt
            num = jnp.einsum("bhkv,bhk->bhv", C, qt, precision=hi)
            den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n, qt,
                                                 precision=hi)),
                              jnp.exp(-m_new))
            return (C, n, m_new), num / den[..., None]

        c0 = (jnp.zeros((B, H, dqk, dv)), jnp.zeros((B, H, dqk)),
              jnp.full((B, H), -1e30))
        sw = lambda t: jnp.moveaxis(t, 1, 0)
        _, hs = jax.lax.scan(step, c0, (sw(q), sw(k), sw(v), sw(log_i),
                                        sw(log_f)))
        hs = jnp.moveaxis(hs, 0, 1).reshape(B, S, di)
        return x + mm(rms(hs, p["m.hnorm"]) * z, p["m.w_down"])

    def slstm(x, p):
        h = rms(x, p["s.norm"])
        gx = mm(h, p["s.w_gates"]) + p["s.b_gates"]
        r = q8(p["s.r_gates"])

        def step(c, gxt):
            cc, n, hh, mx = c
            gr = jnp.einsum("bhd,hdg->bhg", q8(hh.reshape(B, H, dh)), r,
                            precision=hi).reshape(B, 4 * d)
            gi, gf, gz, go = jnp.split(gxt + gr, 4, axis=-1)
            log_f = jax.nn.log_sigmoid(gf)
            m_new = jnp.maximum(log_f + mx, gi)
            ip = jnp.exp(gi - m_new)
            fp = jnp.exp(log_f + mx - m_new)
            cc = fp * cc + ip * jnp.tanh(gz)
            n = fp * n + ip
            hh = jax.nn.sigmoid(go) * cc / jnp.maximum(n, 1e-6)
            return (cc, n, hh, m_new), hh

        z = jnp.zeros((B, d))
        _, hs = jax.lax.scan(step, (z, z, z, jnp.full((B, d), -1e30)),
                             jnp.moveaxis(gx, 1, 0))
        y = rms(jnp.moveaxis(hs, 0, 1), p["s.gnorm"])
        a, b = jnp.split(mm(y, p["s.w_up"]), 2, axis=-1)
        return x + mm(gelu(a) * b, p["s.w_down"])

    x = w["embed"][tokens]
    stacks = {k: v for k, v in w.items() if k[:2] in ("m.", "s.")}

    def layer_pair(x, p):
        return slstm(mlstm(x, p), p), None

    x, _ = jax.lax.scan(layer_pair, x, stacks)
    x = rms(x, w["final_norm"])
    mask = (jnp.arange(S)[None, :] < lengths[:, None]).astype(jnp.float32)
    pooled = (x * mask[..., None]).sum(1) \
        / jnp.maximum(lengths, 1).astype(jnp.float32)[:, None]
    return jnp.matmul(pooled, w["proj"], precision=hi)


@functools.lru_cache(maxsize=None)
def _jitted(model_items: tuple, dtype: str):
    import jax
    m = dict(model_items)
    return jax.jit(functools.partial(_forward, m, dtype))


def features(m: dict, seed: int, texts, dtype: str = "float32",
             w: dict | None = None):
    """(len(texts), n_features) float32 features of ``texts``."""
    import jax.numpy as jnp
    if w is None:
        w = weights(m, seed)
    toks = [tokenize(t, m["seq_len"], m["vocab_size"]) for t in texts]
    tokens = jnp.asarray(np.stack([t for t, _ in toks]))
    lengths = jnp.asarray(np.asarray([n for _, n in toks], np.int32))
    fn = _jitted(tuple(sorted(m.items())), dtype)
    return np.asarray(fn(w, tokens, lengths))
