"""Plain reference of the served models, independent of the program.

A vision-language decoder as published for Qwen2 (RMSNorm, rotary
positions with rotate-half pairs, grouped-query attention with q/k/v
biases, SwiGLU MLP) behind a pre-LN ViT tower whose output is projected to
the decoder width and placed before the instruction tokens. Straight
``jax.numpy`` over the whole sequence: no cache, no kernels, no batching of
requests into slots. It reads the weights the benchmark drew (``weights``),
by name, and imports nothing of the program.

Two precisions:

- ``"f32"``: float32 with every matrix product at ``HIGHEST`` precision —
  the reference the served tokens are judged against;
- ``"fp8"``: the control, one step below the configuration's bfloat16:
  every weight matrix rounded to float8 e4m3 with one scale per output
  channel, activations in bfloat16.

Used layer by layer (a scan over the stacked decoder layers) and for a few
requests at a time, after the engine's state has been freed.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0      # largest finite float8 e4m3fn


def _fp8(w, in_axes):
    """``w`` rounded to float8 e4m3 with one scale per output channel (the
    amax over ``in_axes``), returned in bfloat16."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * scale).astype(jnp.bfloat16)


class _Math:
    """Matrix products and weight reads in one precision."""

    def __init__(self, mode: str):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown reference precision {mode!r}")
        self.mode = mode
        self.dtype = jnp.float32 if mode == "f32" else jnp.bfloat16
        self.precision = HIGHEST if mode == "f32" else None

    def mat(self, w, in_axes):
        if self.mode == "fp8":
            return _fp8(w, in_axes)
        return w.astype(jnp.float32)

    def vec(self, w):
        return w.astype(self.dtype)

    def ein(self, eq, a, b):
        return jnp.einsum(eq, a.astype(self.dtype), b,
                          precision=self.precision,
                          preferred_element_type=jnp.float32
                          ).astype(self.dtype)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _ln(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """Rotate-half rotary embedding at positions 0..S-1; x [B,S,H,h]."""
    S, h = x.shape[1], x.shape[-1]
    half = h // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / h)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _softmax_attend(m, q, k, v, causal):
    """q [B,S,H,h], k/v [B,S,K,h]; query head n reads key head n // G."""
    B, S, H, h = q.shape
    K = k.shape[2]
    qg = q.reshape(B, S, K, H // K, h)
    s = jnp.einsum("bskgh,btkh->bkgst", qg.astype(m.dtype),
                   k.astype(m.dtype), precision=m.precision,
                   preferred_element_type=jnp.float32) / np.sqrt(h)
    if causal:
        mask = np.tril(np.ones((S, S), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p.astype(m.dtype), v.astype(m.dtype),
                   precision=m.precision, preferred_element_type=jnp.float32)
    return o.reshape(B, S, H, h).astype(m.dtype)


def vision_tower(m, W, c, patches):
    """patches [B,T,embed_dim] -> prefix embeddings [B,T,d_model]."""
    v = c["vision"]
    eps = v["ln_eps"]
    x = m.ein("bte,ed->btd", patches, m.mat(W["in_proj"], (0,)))
    x = x + m.vec(W["pos"])[None]

    def layer(x, p):
        y = _ln(x, p["ln1_w"], p["ln1_b"], eps)
        q = m.ein("btd,dnh->btnh", y, m.mat(p["wq"], (0,)))
        k = m.ein("btd,dnh->btnh", y, m.mat(p["wk"], (0,)))
        vv = m.ein("btd,dnh->btnh", y, m.mat(p["wv"], (0,)))
        a = _softmax_attend(m, q, k, vv, causal=False)
        x = x + m.ein("btnh,nhd->btd", a, m.mat(p["wo"], (0, 1)))
        y = _ln(x, p["ln2_w"], p["ln2_b"], eps)
        y = jax.nn.gelu(m.ein("btd,df->btf", y, m.mat(p["wi"], (0,))),
                        approximate=True)
        return x + m.ein("btf,fd->btd", y, m.mat(p["wo_mlp"], (0,))), None

    x, _ = jax.lax.scan(layer, x, W["stack"])
    x = _ln(x, W["final_ln_w"], W["final_ln_b"], eps)
    return m.ein("btd,de->bte", x, m.mat(W["out_proj"], (0,)))


def decoder(m, W, c, x):
    """Causal decoder over x [B,S,d] from position 0; returns the final
    normed hidden states."""
    eps, theta = c["norm_eps"], c["rope_theta"]
    bias = c["qkv_bias"]

    def layer(x, p):
        y = _rms(x, p["ln1_w"], eps)
        q = m.ein("bsd,dnh->bsnh", y, m.mat(p["wq"], (0,)))
        k = m.ein("bsd,dkh->bskh", y, m.mat(p["wk"], (0,)))
        v = m.ein("bsd,dkh->bskh", y, m.mat(p["wv"], (0,)))
        if bias:
            q = q + m.vec(p["bq"])
            k = k + m.vec(p["bk"])
            v = v + m.vec(p["bv"])
        a = _softmax_attend(m, _rope(q, theta), _rope(k, theta), v, True)
        x = x + m.ein("bsnh,nhd->bsd", a, m.mat(p["wo"], (0, 1)))
        y = _rms(x, p["ln2_w"], eps)
        g = m.ein("bsd,df->bsf", y, m.mat(p["wg"], (0,)))
        u = m.ein("bsd,df->bsf", y, m.mat(p["wi"], (0,)))
        y = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32))
        return x + m.ein("bsf,fd->bsd", y, m.mat(p["wo_mlp"], (0,))), None

    x, _ = jax.lax.scan(layer, x, W["decoder"]["blocks"]["sub0"])
    return _rms(x, W["final_norm_w"], eps)


def _head_logits(m, W, c, hid, n_blocks: int = 4):
    """hid [B,R,d] -> logits [B,R,V] float32, the head read in vocab
    blocks so its float32 copy is never whole."""
    head = W["embed"] if c["tie_embeddings"] else W["lm_head"]
    V = head.shape[0]
    step = -(-V // n_blocks)
    outs = []
    for lo in range(0, V, step):
        blk = m.mat(head[lo:lo + step], (1,))
        outs.append(jnp.einsum("brd,vd->brv", hid.astype(m.dtype), blk,
                               precision=m.precision,
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, -1)


@functools.lru_cache(maxsize=None)
def _logits_program(cfg_json: str, mode: str, n_rows: int):
    c = json.loads(cfg_json)
    m = _Math(mode)

    def run(W, patches, tokens):
        prefix = vision_tower(m, W["vision"], c, patches)
        table = _fp8(W["embed"], (1,)) if mode == "fp8" else W["embed"]
        emb = jnp.take(table, tokens, axis=0).astype(m.dtype)
        x = jnp.concatenate([prefix.astype(m.dtype), emb], 1)
        hid = decoder(m, W, c, x)[:, -n_rows:]
        return _head_logits(m, W, c, hid)

    return jax.jit(run)


def logits(W, c: dict, patches, tokens, n_rows: int, mode: str = "f32"):
    """Next-token logits [B, n_rows, V] (float32) at the last ``n_rows``
    positions of the sequence ``patches`` (vision prefix) + ``tokens``."""
    fn = _logits_program(json.dumps(c, sort_keys=True), mode, n_rows)
    with jax.default_matmul_precision("highest" if mode == "f32"
                                      else "default"):
        return fn(W, patches, tokens)


def served_gaps(W, c: dict, patches, prompts, served, control=False,
                block: int = 2):
    """The plain reference run once over each prompt with its served tokens.

    ``patches`` [B,T,e], ``prompts`` [B,P] int, ``served`` [B,n] int (the
    tokens the program produced, greedy). Returns, per request and served
    position, the gap by which the served token's logit lies below the
    reference's best (0 where it is the reference's argmax), and the same
    gap for the token the fp8 control puts first (``control=True`` only;
    else None), as two [B,n] arrays."""
    served = np.asarray(served)
    n = served.shape[1]
    got, ctrl = [], []
    for lo in range(0, served.shape[0], block):
        sl = slice(lo, lo + block)
        toks = np.concatenate([np.asarray(prompts[sl]), served[sl, :-1]], 1)
        px = jnp.asarray(patches[sl])
        tk = jnp.asarray(toks, jnp.int32)
        c_top = (np.asarray(jnp.argmax(logits(W, c, px, tk, n, "fp8"), -1))
                 if control else None)
        ref = logits(W, c, px, tk, n, "f32")
        best = np.asarray(jnp.max(ref, -1))
        at = lambda t: np.asarray(jnp.take_along_axis(
            ref, jnp.asarray(t, jnp.int32)[..., None], -1)[..., 0])
        got.append(best - at(served[sl]))
        if control:
            ctrl.append(best - at(c_top))
        del ref
    return (np.concatenate(got),
            np.concatenate(ctrl) if control else None)
