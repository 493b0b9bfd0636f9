"""The training attention's gradient (``layers._attention_bwd``, the
FlashAttention-2 backward) against ``jax.grad`` of a plain dense softmax
attention written here: full S x S scores with the causal and window
mask, float32 at ``highest`` precision. Each case runs with the backward's
block equal to the forward's chunk, so that the block pairs it skips at
the band's and the triangle's edges are exercised."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers


def dense_attention(q, k, v, *, causal, window, q_offset, k_offset):
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, Sq, KV, H // KV, hd) * hd ** -0.5
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k, precision="highest")
    qp = q_offset + jnp.arange(Sq)[:, None]
    kp = k_offset + jnp.arange(k.shape[1])[None, :]
    ok = jnp.ones(s.shape[-2:], bool)
    if causal:
        ok &= kp <= qp
    if window > 0:
        ok &= qp - kp < window
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v, precision="highest")
    return o.reshape(B, Sq, H, hd)


# (B, Sq, Sk, H, KV, hd, causal, window, q_offset, k_offset, block)
CASES = {
    "causal-full-gqa": (2, 32, 32, 4, 2, 16, True, -1, 0, 0, 8),
    "window-7-block-4-banded": (1, 32, 32, 4, 4, 16, True, 7, 0, 0, 4),
    "window-8-block-4-banded": (1, 32, 32, 4, 4, 16, True, 8, 0, 0, 4),
    "banded-gqa": (1, 32, 32, 8, 2, 16, True, 12, 0, 0, 8),
    "head-dim-24-mha-banded": (1, 32, 32, 4, 4, 24, True, 8, 0, 0, 8),
    "window-wider-than-seq": (1, 16, 16, 4, 4, 16, True, 40, 0, 0, 4),
    "q-offset-window": (1, 16, 32, 4, 2, 16, True, 7, 16, 0, 8),
    "q-and-k-offsets": (1, 16, 16, 4, 2, 16, True, -1, 24, 8, 8),
    "not-causal": (2, 16, 24, 4, 2, 16, False, -1, 0, 0, 8),
}


def _inputs(B, Sq, Sk, H, KV, hd, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (B, Sq, H, hd)),
            jax.random.normal(ks[1], (B, Sk, KV, hd)),
            jax.random.normal(ks[2], (B, Sk, KV, hd)),
            jax.random.normal(ks[3], (B, Sq, H, hd)))


@pytest.mark.parametrize("mode", ["static", "one-row-forward", "traced"])
@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_vjp_matches_dense_attention(case, mode, monkeypatch):
    """dq, dk, dv within 1e-5 of the dense attention's (float32: only the
    order of the sums differs, blockwise online softmax against one
    softmax; measured ~5e-7). ``one-row-forward``: the banded training
    forward takes its query rows one at a time. ``traced``: the window
    and the offsets are traced values under jit, so the backward visits
    every block pair and the mask alone keeps what each query sees."""
    B, Sq, Sk, H, KV, hd, causal, window, qo, ko, blk = CASES[case]
    traced = mode == "traced"
    monkeypatch.setattr(layers, "BWD_BLOCK", blk)
    if mode == "one-row-forward":
        monkeypatch.setattr(layers, "FWD_SCORE_BYTES", 1)
    q, k, v, g = _inputs(B, Sq, Sk, H, KV, hd)

    def loss(q, k, v, window, qo, ko):
        o = layers.chunked_attention(q, k, v, causal=causal, window=window,
                                     q_offset=qo, k_offset=ko, chunk=blk)
        return jnp.vdot(o, g)

    if traced:
        got = jax.jit(jax.grad(loss, (0, 1, 2)))(
            q, k, v, jnp.int32(window), jnp.int32(qo), jnp.int32(ko))
    else:
        got = jax.grad(loss, (0, 1, 2))(q, k, v, window, qo, ko)
    want = jax.grad(lambda q, k, v: jnp.vdot(dense_attention(
        q, k, v, causal=causal, window=window, q_offset=qo, k_offset=ko),
        g), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert err < 1e-5, (name, err)


@pytest.mark.parametrize("case,score_bytes", [
    ("causal-full-gqa", None), ("window-7-block-4-banded", None),
    ("window-7-block-4-banded", 1), ("q-offset-window", None)])
def test_forward_under_the_vjp_is_the_primal(case, score_bytes, monkeypatch):
    """The forward that keeps the log-sum-exp gives, bit for bit, the
    output of the forward that is not differentiated (bfloat16 in, as
    trained), also where the banded training forward takes its query
    rows one at a time (``score_bytes`` 1)."""
    B, Sq, Sk, H, KV, hd, causal, window, qo, ko, blk = CASES[case]
    if score_bytes:
        monkeypatch.setattr(layers, "FWD_SCORE_BYTES", score_bytes)
    q, k, v, _ = (x.astype(jnp.bfloat16)
                  for x in _inputs(B, Sq, Sk, H, KV, hd, seed=1))
    f = lambda q, k, v: layers.chunked_attention(
        q, k, v, causal=causal, window=window, q_offset=qo, k_offset=ko,
        chunk=blk)
    primal = jax.jit(f)(q, k, v)
    under_vjp = jax.jit(lambda q, k, v: jax.vjp(f, q, k, v)[0])(q, k, v)
    assert primal.dtype == under_vjp.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(primal, np.float32),
                                  np.asarray(under_vjp, np.float32))


def _grad_temp(S, H=4, KV=4, hd=16, blk=64):
    sd = jax.ShapeDtypeStruct
    f = jax.jit(jax.grad(lambda q, k, v: jnp.sum(layers.chunked_attention(
        q, k, v, causal=True, chunk=blk)), (0, 1, 2)))
    c = f.lower(sd((1, S, H, hd), jnp.float32), sd((1, S, KV, hd),
                jnp.float32), sd((1, S, KV, hd), jnp.float32)).compile()
    return c.memory_analysis().temp_size_in_bytes


def test_grad_keeps_no_score_matrix(monkeypatch):
    """The CPU compile of a jitted grad at S = 512 (blocks of 64) keeps
    under half of one S x S x H float32 score matrix (XLA's own VJP of the
    blockwise forward kept 3.1 of them), and doubling S at most about
    doubles it: nothing in it grows as S x S."""
    monkeypatch.setattr(layers, "BWD_BLOCK", 64)
    S, H = 512, 4
    t1, t2 = _grad_temp(S, H), _grad_temp(2 * S, H)
    assert t1 < S * S * H * 4 / 2, t1
    assert t2 < 2.5 * t1, (t1, t2)
