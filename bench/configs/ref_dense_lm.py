"""Plain reference of a dense decoder LM (Qwen3, Phi-3): forward, loss,
gradients and the optimizer step, in float32 at ``highest`` precision.

Straight ``jax.numpy`` from the published description, with no kernel, no
cache and no batching beyond what fits: it imports nothing of the program
and is given nothing the program made. Weights come from ``bench.weights``
and the seed. It runs layer by layer (``jax.checkpoint`` per layer, query
blocks in attention, row chunks in the loss) so that it fits beside
nothing else on one chip.

``mode`` picks the arithmetic of every matmul operand: ``"f32"`` is the
reference; ``"fp8"`` rounds each operand to float8 e4m3 with one scale per
tensor first (the control: the step below the bfloat16 the configurations
state). ``rows`` keeps only the first rows of each batch (a fault: half of
the batch left out, the mean taken over the rest).

Layer equations (per token, residual stream ``h``):
  a = rms(h) * (1 + ln);  q = a wq;  [k v] = a wkv   (columns (KV, 2, hd))
  q, k = rms_head(q) * (1 + q_norm), rms_head(k) * (1 + k_norm)  (qk_norm)
  q, k = rope(q), rope(k)          (rotate halves, theta ** (-2i / hd))
  o = softmax(q k^T / sqrt(hd), causal [and q - k < window]) v, head j
      reading kv head j // (H / KV)
  h = h + o wo;  a = rms(h) * (1 + fln);  h = h + (silu(a wg) * (a wu)) wd
  logits = rms(h) * (1 + final_norm) @ head,  head = embed^T when tied
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # attention query rows per block
LOSS_ROWS = 512        # loss positions per chunk
FP8_MAX = 448.0        # largest finite float8 e4m3


def _fp8(x):
    """x rounded to float8 e4m3 under one scale per tensor; the gradient
    passes through the rounding unchanged."""
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / FP8_MAX)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(mode):
    def mm(a, b, spec=None):
        if mode == "fp8":
            a, b = _fp8(a), _fp8(b)
        if spec is None:
            return jnp.matmul(a, b, precision=HIGHEST)
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return mm


def rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + s)


def rope(x, pos, theta):
    """x (..., S, heads, hd), pos (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, window, mm):
    """q (B,S,H,hd), k/v (B,S,KV,hd) -> (B,S,H*hd); causal, optional
    sliding window, in blocks of query rows."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd) / math.sqrt(hd)
    kpos = jnp.arange(S)
    outs = []
    for lo in range(0, S, Q_BLOCK):
        qb = qg[:, lo:lo + Q_BLOCK]
        qpos = lo + jnp.arange(qb.shape[1])

        @jax.checkpoint
        def block(qb, k, v, qpos=qpos):
            s = mm(qb, k, "bqkgd,bskd->bkgqs")
            ok = kpos[None, :] <= qpos[:, None]
            if window and window > 0:
                ok &= (qpos[:, None] - kpos[None, :]) < window
            s = jnp.where(ok, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return mm(p, v, "bkgqs,bskd->bqkgd")
        outs.append(block(qb, k, v))
    return jnp.concatenate(outs, 1).reshape(B, S, H * hd)


def layer(c, w, h, mode):
    """One decoder layer on h (B, S, D)."""
    mm = _mm(mode)
    eps = c["rms_norm_eps"]
    B, S, _ = h.shape
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    pos = jnp.arange(S)
    a = rms(h, w["ln"], eps)
    q = mm(a, w["wq"]).reshape(B, S, H, hd)
    kv = mm(a, w["wkv"]).reshape(B, S, KV, 2, hd)
    k, v = kv[:, :, :, 0], kv[:, :, :, 1]
    if c.get("qk_norm"):
        q = rms(q, w["q_norm"], eps)
        k = rms(k, w["k_norm"], eps)
    q = rope(q, pos, c["rope_theta"])
    k = rope(k, pos, c["rope_theta"])
    o = attention(q, k, v, c.get("sliding_window") or 0, mm)
    h = h + mm(o, w["wo"])
    a = rms(h, w["fln"], eps)
    return h + mm(jax.nn.silu(mm(a, w["wg"])) * mm(a, w["wu"]), w["wd"])


def head_matrix(c, top):
    return top["embed"].T if c["tie_word_embeddings"] else top["lm_head"]


def loss_sum(c, params, tokens, labels, mode):
    """Summed next-token cross-entropy over (B, S) tokens."""
    top, layers = params["top"], params["layers"]
    h = top["embed"][tokens]
    body = jax.checkpoint(lambda h, w: (layer(c, w, h, mode), None))
    h, _ = jax.lax.scan(body, h, layers)
    hn = rms(h, top["final_norm"], c["rms_norm_eps"])
    head = head_matrix(c, top)
    mm = _mm(mode)
    total = 0.0
    for lo in range(0, hn.shape[1], LOSS_ROWS):
        @jax.checkpoint
        def chunk(hc, head, lc):
            z = mm(hc, head)
            lse = jax.nn.logsumexp(z, -1)
            zl = jnp.take_along_axis(z, lc[..., None], -1)[..., 0]
            return jnp.sum(lse - zl)
        total = total + chunk(hn[:, lo:lo + LOSS_ROWS], head,
                              labels[:, lo:lo + LOSS_ROWS])
    return total


# ------------------------------------------------------------ the optimizer
# Moments as the configuration stores them: int8 codes per last-dim row
# with the row's largest magnitude; signed square-root companding for the
# first moment, fourth-root companding for the second.
def _code_m(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-12)
    q = jnp.round(127.0 * jnp.sign(x) * jnp.sqrt(jnp.abs(x) / amax))
    return q.astype(jnp.int8), amax


def _value_m(q, amax):
    q = q.astype(jnp.float32)
    return jnp.sign(q) * jnp.square(q / 127.0) * amax


def _code_v(x):
    amax = jnp.maximum(jnp.max(x, -1, keepdims=True), 1e-20)
    q = jnp.round(127.0 * jnp.power(x / amax, 0.25))
    return q.astype(jnp.int8), amax


def _value_v(q, amax):
    return jnp.power(q.astype(jnp.float32) / 127.0, 4.0) * amax


def _zero_moments(p):
    z = lambda: jnp.zeros(p.shape, jnp.int8)
    s = lambda: jnp.zeros(p.shape[:-1] + (1,), jnp.float32)
    return (z(), s(), z(), s())


def _adam_step(opt):
    b1, b2, eps, lr, wd = (opt["b1"], opt["b2"], opt["eps"], opt["lr"],
                           opt["weight_decay"])

    def leaf(p, mom, g, t, decay):
        m = b1 * _value_m(mom[0], mom[1]) + (1 - b1) * g
        v = b2 * _value_v(mom[2], mom[3]) + (1 - b2) * g * g
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        p = p - lr * (upd + (wd if decay else 0.0) * p)
        return p, _code_m(m) + _code_v(v)
    return jax.jit(leaf, static_argnums=(4,), donate_argnums=(0, 1))


def init_params(c, seed):
    key = W.seed_key(seed)
    L = c["num_hidden_layers"]
    layers = jax.jit(lambda key: jax.vmap(
        lambda l: W.layer_weights(key, c, l))(jnp.arange(L)))(key)
    top = {n: jax.jit(lambda key, n=n: W.top_tensor(key, c, n))(key)
           for n in W.top_shapes(c)}
    return {"top": top, "layers": layers}


def leaf_norms(tree):
    """Name -> float32 norm of each leaf: each tensor of each layer on its
    own, and the top tensors."""
    out = {}
    for n, a in tree["top"].items():
        out[f"top.{n}"] = float(jnp.linalg.norm(a.astype(jnp.float32)))
    for n, a in tree["layers"].items():
        norms = jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                 axis=tuple(range(1, a.ndim))))
        for l, x in enumerate(np.asarray(norms)):
            out[f"layer{l}.{n}"] = float(x)
    return out


def train(c, opt, seed, batches, *, mode="f32", rows=None):
    """``len(batches)`` optimizer steps from the seeded weights: mean
    cross-entropy over the batch, global-norm clip, AdamW.

    ``batches``: list of (tokens, labels) int32 arrays (B, S). Returns the
    loss and the pre-clip gradient norm of each step, the norms of the
    first step's clipped gradient per leaf, and the norms of each leaf's
    change over all steps."""
    params = init_params(c, seed)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss_sum(c, p, t, l, mode)))
    adam = _adam_step(opt)
    moments = jax.tree.map(_zero_moments, params)
    losses, gnorms, first_grad = [], [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        tokens, labels = np.asarray(tokens), np.asarray(labels)
        if rows is not None:
            tokens, labels = tokens[:rows], labels[:rows]
        total, grads = grad_fn(params, tokens, labels)
        n = tokens.size
        gnorm = math.sqrt(sum(float(jnp.sum(jnp.square(g)))
                              for g in jax.tree.leaves(grads))) / n
        scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-12)) / n
        grads = jax.tree.map(lambda g: g * scale, grads)
        losses.append(float(total) / n)
        gnorms.append(gnorm)
        if t == 1:
            first_grad = leaf_norms(grads)
        for part in ("top", "layers"):
            for name in params[part]:
                # the configuration decays every tensor stored with a
                # layer axis or two dims; the final norm alone is exempt
                decay = part == "layers" or params[part][name].ndim > 1
                params[part][name], moments[part][name] = adam(
                    params[part][name], moments[part][name],
                    grads[part][name], float(t), decay)
                grads[part][name] = None
        del grads
    del moments
    init = init_params(c, seed)
    change = leaf_norms(jax.tree.map(jnp.subtract, params, init))
    return {"losses": losses, "gnorms": gnorms, "first_grad": first_grad,
            "change": change}


# ----------------------------------------------------------------- serving
def _serve_layer(c, weight_dtype, mode):
    @jax.jit
    def run(h, l, key):
        w = jax.tree.map(lambda a: a.astype(jnp.float32),
                         W.layer_weights(key, c, l, weight_dtype))
        return layer(c, w, h, mode)
    return run


def teacher_forced(c, seed, tokens, probe=None, *,
                   weight_dtype=jnp.bfloat16, mode="f32"):
    """Next-token logits over ``tokens`` (N, S), one row at a time and
    layer by layer, the weights as served (rounded to ``weight_dtype``).
    Returns (N, S, 4): at each position the largest logit, its token id,
    the logit of ``tokens[n, s + 1]`` (0 at the last position) and the
    logit of ``probe[n, s]`` (0 without ``probe``)."""
    key = W.seed_key(seed)
    top = {n: jax.jit(lambda key, n=n: W.top_tensor(key, c, n, weight_dtype)
                      .astype(jnp.float32))(key)
           for n in W.top_shapes(c)}
    tokens = jnp.asarray(tokens, jnp.int32)
    nxt = jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)
    probe = jnp.zeros_like(tokens) if probe is None else jnp.asarray(
        probe, jnp.int32)
    mm = _mm(mode)

    @jax.jit
    def read(h, top, nxt, probe):
        hn = rms(h, top["final_norm"], c["rms_norm_eps"])
        z = mm(hn, head_matrix(c, top))                     # (1, S, V)
        pick = lambda ids: jnp.take_along_axis(z, ids[..., None], -1)[..., 0]
        return jnp.stack([z.max(-1), jnp.argmax(z, -1).astype(jnp.float32),
                          pick(nxt), pick(probe)], -1)

    run = _serve_layer(c, weight_dtype, mode)
    rows = []
    for n in range(tokens.shape[0]):
        h = top["embed"][tokens[n:n + 1]]
        for l in range(c["num_hidden_layers"]):
            h = run(h, l, key)
        rows.append(np.asarray(read(h, top, nxt[n:n + 1],
                                    probe[n:n + 1]))[0])
    return np.stack(rows)
