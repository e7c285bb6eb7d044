"""Plain reference of the dense decoder: RMSNorm, multi-head attention with
RoPE (and an optional sliding window), SwiGLU MLP, untied LM head,
next-token cross-entropy.

Written from the published description (LLaMA-style blocks as DeepSeek-LLM
and Phi-3 use them) in straightforward ``jax.numpy``: full attention
scores with a causal mask, no kernels, no windows inside the model.  It
reads the parameter tree by name:

    embed [V, D]   head [D, V]   final_norm [D]
    layers/ln1, layers/ln2                    [L, D]
    layers/attn/wq [L, D, H, hd]   wk, wv [L, D, KV, hd]   wo [L, H, hd, D]
    layers/mlp/w_gate, w_up [L, D, F]   w_down [L, F, D]

Departures from the published models, all shared with the program under
test: weights are random (the seed's), the vocabulary may be a slice, the
depth is cut, and training runs in float32 where the checkpoints are
bfloat16.  ``dtype`` computes everything in another type (the control).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Dims:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    eps: float
    theta: float
    window: int          # sliding attention window, 0 = none


def dims(config: dict) -> Dims:
    """Reference sizes from a configuration file's public keys."""
    return Dims(n_heads=config["num_attention_heads"],
                n_kv_heads=config["num_key_value_heads"],
                head_dim=config["head_dim"], eps=config["rms_norm_eps"],
                theta=config["rope_theta"],
                window=config.get("sliding_window") or 0)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, theta):
    """Rotary embedding, rotate-half form; x ``[B, S, H, hd]``."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x * jnp.cos(ang).astype(x.dtype)
            + rot * jnp.sin(ang).astype(x.dtype))


def attention(q, k, v, window):
    """Causal softmax attention; q ``[B, S, H, hd]``, k/v ``[B, S, KV, hd]``
    (each kv head serves ``H / KV`` consecutive query heads)."""
    S, H, hd = q.shape[1], q.shape[2], q.shape[3]
    group = H // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    i = jnp.arange(S)
    allowed = i[:, None] >= i[None, :]
    if window:
        allowed &= (i[:, None] - i[None, :]) < window
    s = jnp.where(allowed, s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def block(p, h, d: Dims):
    x = rms_norm(h, p["ln1"], d.eps)
    a = p["attn"]
    q = rope(jnp.einsum("bsd,dhe->bshe", x, a["wq"]), d.theta)
    k = rope(jnp.einsum("bsd,dhe->bshe", x, a["wk"]), d.theta)
    v = jnp.einsum("bsd,dhe->bshe", x, a["wv"])
    h = h + jnp.einsum("bshe,hed->bsd", attention(q, k, v, d.window),
                       a["wo"])
    x = rms_norm(h, p["ln2"], d.eps)
    m = p["mlp"]
    return h + (jax.nn.silu(x @ m["w_gate"]) * (x @ m["w_up"])) @ m["w_down"]


def loss(params, tokens, d: Dims):
    """Mean next-token cross-entropy of ``tokens [B, S]``."""
    h = params["embed"][tokens]

    def body(h, p):
        return block(p, h, d), None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, params["layers"])
    h = rms_norm(h, params["final_norm"], d.eps)
    logits = (h @ params["head"])[:, :-1]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)
