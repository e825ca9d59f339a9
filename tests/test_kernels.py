"""Per-kernel validation: shape/dtype sweeps, interpret-mode Pallas vs the
pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.linear_scan import linear_scan
from repro.kernels.uncertainty import entropy_scores
from repro.kernels.xent import streaming_xent

KEY = jax.random.key(42)


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Sk,D", [
    (2, 4, 2, 256, 256, 64),
    (1, 8, 8, 384, 384, 128),
    (2, 4, 1, 128, 512, 64),     # MQA, cross-length
    (1, 2, 2, 200, 200, 64),     # ragged (padding path)
    (1, 6, 2, 256, 256, 128),    # GQA group 3
])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, Hq, Hkv, Sq, Sk, D, causal, window, dtype):
    if not causal and Sq != Sk:
        pytest.skip("cross-shape covered by causal=False equal-length case")
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("B,S,D", [(1, 64, 64), (3, 300, 150), (8, 256, 128),
                                   (2, 1000, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_linear_scan(B, S, D, dtype):
    ks = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, D))).astype(dtype)
    b = jax.random.normal(ks[1], (B, S, D), dtype)
    h0 = jax.random.normal(ks[2], (B, D), dtype)
    out = linear_scan(a, b, h0, interpret=True)
    expect = ref.linear_scan_ref(a.astype(jnp.float32),
                                 b.astype(jnp.float32),
                                 h0.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect),
                               atol=20 * tol(dtype), rtol=20 * tol(dtype))


def test_linear_scan_matches_sequential():
    a = jax.nn.sigmoid(jax.random.normal(KEY, (2, 50, 7)))
    b = jax.random.normal(KEY, (2, 50, 7))
    h = np.zeros((2, 7))
    seq = []
    an, bn = np.asarray(a), np.asarray(b)
    for t in range(50):
        h = an[:, t] * h + bn[:, t]
        seq.append(h.copy())
    seq = np.stack(seq, 1)
    out = linear_scan(a, b, None, interpret=True)
    np.testing.assert_allclose(np.asarray(out), seq, atol=1e-5)


@pytest.mark.parametrize("N,V", [(10, 100), (100, 1000), (64, 50304),
                                 (33, 777)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_entropy(N, V, dtype):
    x = (jax.random.normal(KEY, (N, V)) * 4).astype(dtype)
    out = entropy_scores(x, interpret=True)
    expect = ref.entropy_ref(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=max(tol(dtype), 1e-4) * 10, rtol=1e-2)
    # entropy bounds: [0, log V]
    assert (np.asarray(out) >= -1e-3).all()
    assert (np.asarray(out) <= np.log(V) + 1e-3).all()


@pytest.mark.parametrize("N,V", [(10, 100), (64, 50304), (33, 777)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_streaming_xent(N, V, dtype):
    x = (jax.random.normal(KEY, (N, V)) * 3).astype(dtype)
    t = jax.random.randint(KEY, (N,), 0, V)
    out = streaming_xent(x, t, interpret=True)
    expect = ref.xent_ref(x, t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=max(tol(dtype) * 10, 1e-4), rtol=1e-2)


# ---------------------------------------------------------------------------
# entropy at learner widths: the active-learning scorer runs entropy over
# class posteriors for a whole candidate pool — many rows, few columns —
# the transpose of the LM-vocab regime the sweep above covers.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("N,C", [
    (256, 2),       # minimal classes, lane-width rows
    (384, 10),      # non-pow-2 rows
    (512, 64),      # widest class count the scenarios use
    (777, 17),      # both dims non-pow-2
    (1024, 48),     # largest candidate pool, non-pow-2 classes
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_entropy_learner_widths(N, C, dtype):
    x = (jax.random.normal(jax.random.fold_in(KEY, N * C), (N, C)) * 3
         ).astype(dtype)
    out = entropy_scores(x, interpret=True)
    expect = ref.entropy_ref(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=max(tol(dtype), 1e-4) * 10, rtol=1e-2)
    assert (np.asarray(out) >= -1e-3).all()
    assert (np.asarray(out) <= np.log(C) + 1e-3).all()


@pytest.mark.parametrize("B,N,C", [(4, 300, 8), (3, 256, 33)])
def test_entropy_vmapped(B, N, C):
    """The grid engine maps the scorer over scenario cells; the kernel
    must survive a batch axis added by vmap, matching per-row calls."""
    x = jax.random.normal(KEY, (B, N, C)) * 3
    out = jax.vmap(lambda r: entropy_scores(r, interpret=True))(x)
    assert out.shape == (B, N)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(out[b]),
                                   np.asarray(ref.entropy_ref(x[b])),
                                   atol=1e-3, rtol=1e-2)


def test_uncertainty_topk_selects_most_uncertain():
    from repro.kernels.ops import uncertainty_topk
    # rows with increasing temperature -> increasing entropy
    logits = jnp.stack([jnp.array([10.0, 0, 0, 0]),
                        jnp.array([2.0, 0, 0, 0]),
                        jnp.array([0.1, 0, 0, 0]),
                        jnp.array([0.0, 0, 0, 0])])
    scores, idx = uncertainty_topk(logits, 2)
    assert set(np.asarray(idx).tolist()) == {2, 3}
