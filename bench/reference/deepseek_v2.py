"""Plain reference of a DeepSeek-V2 federated sub-model round: latent
attention (MLA) with YaRN rope, a leading dense SwiGLU layer, then expert
layers of routed and shared SwiGLU experts, on one chip's share of the
experts.

Written from the published description (arXiv:2405.04434 and the model's
``config.json``) in straightforward ``jax.numpy``, float32, under the
highest matmul precision; ``dtype`` runs the whole round in another type
(the control) and ``precision`` at another matmul precision, as
``bench/reference/round.py``, whose interface this module shares.  It
reads the configuration file's public keys and never its ``"program"``
group:

- MLA, decompressed: q from ``wq`` (no q compression, ``q_lora_rank``
  null) or from ``w_dq``, ``q_norm`` and ``w_uq``; the compressed kv
  ``c = rms_norm(x w_dkv)``, per head ``k_nope = c w_uk`` and
  ``v = c w_uv``, one rope key ``x w_kr`` shared by every head; full
  causal scores.  YaRN (``rope_scaling``): DeepSeek-V2's blend of
  interpolated and extrapolated rope frequencies across the correction
  range, the cos/sin factor mscale / mscale_all_dim, and the softmax scale
  1/sqrt(qk head dim) times mscale_all_dim's factor squared.
- The dense layers (``first_k_dense_replace``): a SwiGLU MLP.
- An expert layer, computed densely: the softmax over the router's every
  expert (``published.n_routed_experts`` of them), the greedy top-k of
  those probabilities, renormalised only with ``norm_topk_prob``; every
  expert of the window on every token, times its probability where it was
  chosen, else 0; then the shared experts' SwiGLU (``n_shared_experts``
  times the expert width), and the sequence-wise balance loss
  (``seq_aux``): per sequence, ``alpha * sum_i f_i P_i`` with
  ``f_i = count_i / (S k / E)`` over every choice and ``P_i`` the mean
  probability, averaged over the sequences and added per layer.

The round is ``round.py``'s: extract every client's window, K plain SGD
steps, the mean of the scattered deltas, the server step.  Rolling windows
follow the program's schedule on every axis present: the dense MLP width,
the attention heads, the held experts, and the expert width at both its
sizes (one expert's, and the shared experts' together); the router has
none.

Departures from the published model, each shared with the program under
test: the depth is cut to the file's ``num_hidden_layers``; this chip
holds the first ``n_routed_experts`` of the router's experts (ids
``0 .. n-1``), and a choice of an expert held elsewhere adds nothing; the
vocabulary is a slice; weights are random and train in float32 where the
checkpoint is bfloat16; and rope rotates halves where the checkpoint
rotates interleaved pairs, a fixed permutation of the 64 rope columns of
``wq`` and ``w_kr`` that random weights cannot tell apart.

``model_flops`` counts what the round needs as ``round.py`` does: 2 FLOPs
per multiply-add, backward twice the forward, every projection over the
window's heads and widths, the router, the LM head and causal attention;
the routed experts at their expected load, ``T k win / E`` rows of each
layer's ``T`` tokens (``win`` of the router's ``E`` experts in the window),
since the rows a round routes depend on its data.  Recompute under remat
is not counted.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.model import rms_norm
from bench.reference.round import _grid, causal_pairs

#: Windowed axes: (name, config size of the full axis).
AXES = ("ff", "heads", "experts", "eff", "sff")


@dataclass(frozen=True)
class Dims:
    n_heads: int
    nope: int
    rope: int
    v: int
    q_rank: int          # 0: no q compression
    eps: float
    inv_freq: tuple      # rope inverse frequencies [rope / 2]
    rope_scale: float    # cos/sin factor
    softmax_scale: float
    top_k: int
    n_router: int        # experts the router spans
    norm_topk: bool
    alpha: float         # balance-loss weight


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(dim, theta, beta_fast, beta_slow, original_max):
    """``(low, high)``: the first frequency index that turns fewer than
    ``beta_fast`` times over the original context, floored, and the last
    that turns more than ``beta_slow`` times, ceiled."""
    def d(rot):
        return (dim * math.log(original_max / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(d(beta_fast)), 0),
            min(math.ceil(d(beta_slow)), dim - 1))


def rope_inv_freq(config: dict) -> np.ndarray:
    dim, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    i = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / theta ** i
    rs = config.get("rope_scaling")
    if not rs:
        return extra
    inter = 1.0 / (rs["factor"] * theta ** i)
    low, high = yarn_range(dim, theta, rs["beta_fast"], rs["beta_slow"],
                           rs["original_max_position_embeddings"])
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def dims(config: dict) -> Dims:
    rs = config.get("rope_scaling") or {}
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    scale = 1.0 / math.sqrt(qk)
    rope_scale = 1.0
    if rs:
        f = rs["factor"]
        rope_scale = (yarn_mscale(f, rs.get("mscale", 1.0))
                      / yarn_mscale(f, rs.get("mscale_all_dim", 0.0)))
        if rs.get("mscale_all_dim"):
            scale *= yarn_mscale(f, rs["mscale_all_dim"]) ** 2
    return Dims(n_heads=config["num_attention_heads"],
                nope=config["qk_nope_head_dim"],
                rope=config["qk_rope_head_dim"], v=config["v_head_dim"],
                q_rank=config.get("q_lora_rank") or 0,
                eps=config["rms_norm_eps"],
                inv_freq=tuple(float(f) for f in rope_inv_freq(config)),
                rope_scale=rope_scale, softmax_scale=scale,
                top_k=config["num_experts_per_tok"],
                n_router=config.get("published", {}).get(
                    "n_routed_experts", config["n_routed_experts"]),
                norm_topk=bool(config.get("norm_topk_prob", True)),
                alpha=float(config.get("aux_loss_alpha", 0.0)
                            if config.get("seq_aux", True) else 0.0))


def rope(x, d: Dims):
    """Rope of ``x [B, S, H, r]``, rotate-half form."""
    S = x.shape[1]
    inv = jnp.asarray(d.inv_freq, jnp.float32)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x * (jnp.cos(ang) * d.rope_scale).astype(x.dtype)
            + rot * (jnp.sin(ang) * d.rope_scale).astype(x.dtype))


def mla(a, x, d: Dims):
    """Causal latent attention of ``x [B, S, D]`` over the window's heads."""
    if d.q_rank:
        cq = rms_norm(x @ a["w_dq"], a["q_norm"], d.eps)
        q = jnp.einsum("bsr,rhe->bshe", cq, a["w_uq"])
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, a["wq"])
    q = jnp.concatenate([q[..., :d.nope], rope(q[..., d.nope:], d)], -1)
    c = rms_norm(x @ a["w_dkv"], a["kv_norm"], d.eps)
    k_nope = jnp.einsum("bsr,rhe->bshe", c, a["w_uk"])
    v = jnp.einsum("bsr,rhe->bshe", c, a["w_uv"])
    k_rope = rope((x @ a["w_kr"])[:, :, None, :], d)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, k_nope.shape[:3] + (d.rope,))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d.softmax_scale
    i = jnp.arange(x.shape[1])
    s = jnp.where(i[:, None] >= i[None, :], s, jnp.finfo(s.dtype).min)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bshe,hed->bsd", o, a["wo"])


def swiglu(m, x):
    return (jax.nn.silu(x @ m["w_gate"]) * (x @ m["w_up"])) @ m["w_down"]


def experts(m, x, lo, d: Dims):
    """``(out, balance loss)`` of the expert layer on ``x [B, S, D]``; the
    window holds router ids ``lo .. lo + win - 1``."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    probs = jax.nn.softmax(xt @ m["router"], axis=-1)          # [T, E]
    top, idx = jax.lax.top_k(probs, d.top_k)
    chosen = jax.nn.one_hot(idx, d.n_router, dtype=probs.dtype).sum(1)
    weight = probs * chosen
    if d.norm_topk:
        weight = weight / top.sum(-1, keepdims=True)
    win = m["w_gate"].shape[0]
    gate = jax.lax.dynamic_slice_in_dim(weight, lo, win, axis=1)  # [T, win]
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, m["w_gate"])) \
        * jnp.einsum("td,edf->tef", xt, m["w_up"])
    y = jnp.einsum("tef,efd,te->td", h, m["w_down"], gate)
    y = y + swiglu(m["shared"], xt)
    f = chosen.reshape(B, S, -1).sum(1) / (S * d.top_k / d.n_router)
    P = probs.reshape(B, S, -1).mean(1)
    aux = d.alpha * jnp.mean(jnp.sum(f * P, axis=-1))
    return y.reshape(B, S, D), aux


def loss(params, tokens, lo, d: Dims):
    """Mean next-token cross-entropy of ``tokens [B, S]`` plus every expert
    layer's balance loss."""
    h = params["embed"][tokens]

    def dense(h, p):
        h = h + mla(p["attn"], rms_norm(h, p["ln1"], d.eps), d)
        return h + swiglu(p["mlp"], rms_norm(h, p["ln2"], d.eps)), None

    def moe(carry, p):
        h, aux = carry
        h = h + mla(p["attn"], rms_norm(h, p["ln1"], d.eps), d)
        y, a = experts(p["moe"], rms_norm(h, p["ln2"], d.eps), lo, d)
        return (h + y, aux + a), None

    h, _ = jax.lax.scan(jax.checkpoint(dense), h, params["dense_layers"])
    (h, aux), _ = jax.lax.scan(jax.checkpoint(moe),
                               (h, jnp.zeros((), h.dtype)),
                               params["moe_layers"])
    h = rms_norm(h, params["final_norm"], d.eps)
    logits = (h @ params["head"])[:, :-1]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - picked) + aux


# -- windows -------------------------------------------------------------------


@dataclass(frozen=True)
class Plan:
    """Full and window sizes of each windowed axis, and the windows an
    epoch of the rolling schedule runs through."""

    full: dict
    win: dict
    n_windows: int


def plan(config: dict, capacity: float) -> Plan:
    F = config["moe_intermediate_size"]
    full = {"ff": config["intermediate_size"],
            "heads": config["num_attention_heads"],
            "experts": config["n_routed_experts"], "eff": F,
            "sff": config["n_shared_experts"] * F}
    win = {k: max(1, int(round(capacity * n))) for k, n in full.items()}
    R = max(math.ceil(full[k] / win[k]) for k in AXES)
    return Plan(full, win, R)


def offsets(p: Plan, window_seed: int, round_idx: int, clients: int,
            stagger: bool) -> dict:
    """``{axis: [C] offsets}`` of round ``round_idx``."""
    R = p.n_windows
    e, r = divmod(round_idx, R)
    perm = np.asarray(jax.random.permutation(
        jax.random.fold_in(jax.random.PRNGKey(window_seed), e), R))
    idx = perm[(r + np.arange(clients)) % R] if stagger \
        else np.full(clients, perm[r])
    return {k: _grid(p.full[k], p.win[k], R)[idx] for k in AXES}


def _window(path, leaf, p: Plan):
    """``[(dim, axis)]`` of a stacked leaf's windowed dims."""
    name, group = path[-1], path[-2] if len(path) > 1 else ""
    if group == "mlp":
        return [(2 if name != "w_down" else 1, "ff")]
    if group == "attn" and name in ("wq", "w_uq", "w_uk", "w_uv"):
        return [(2, "heads")]
    if group == "attn" and name == "wo":
        return [(1, "heads")]
    if group == "shared":
        return [(2 if name != "w_down" else 1, "sff")]
    if group == "moe" and name in ("w_gate", "w_up", "w_down"):
        return [(1, "experts"), (3 if name != "w_down" else 2, "eff")]
    return []


def _map(fn, tree, path=()):
    if isinstance(tree, dict):
        return {k: _map(fn, v, path + (k,)) for k, v in tree.items()}
    return fn(path, tree)


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def extract(params, offs: dict, p: Plan):
    def leaf(path, x):
        for dim, axis in _window(path, x, p):
            x = jax.lax.dynamic_slice_in_dim(x, offs[axis], p.win[axis],
                                             axis=dim)
        return x
    return _map(leaf, params)


def add_scattered(acc, delta, offs: dict, p: Plan):
    """``acc`` plus ``delta`` (sub-model shaped) placed in its window."""
    def leaf(path, a):
        d = _get(delta, path)
        starts = [0] * a.ndim
        for dim, axis in _window(path, a, p):
            starts[dim] = offs[axis]
        cur = jax.lax.dynamic_slice(a, starts, d.shape)
        return jax.lax.dynamic_update_slice(a, cur + d, starts)
    return _map(leaf, acc)


# -- FLOPs -----------------------------------------------------------------


def model_flops(config: dict, mix: dict) -> float:
    """FLOPs the sub-model needs per round, summed over clients and local
    steps (the module's doc says what is counted)."""
    p = plan(config, mix["capacity"])
    d = dims(config)
    D, V = config["hidden_size"], config["vocab_size"]
    L = config["num_hidden_layers"]
    L_dense = config.get("first_k_dense_replace", 0)
    L_moe = L - L_dense
    r = config["kv_lora_rank"]
    Hw = p.win["heads"]
    qk = d.nope + d.rope
    q = (D * d.q_rank + d.q_rank * Hw * qk) if d.q_rank else D * Hw * qk
    attn = q + D * (r + d.rope) + r * Hw * (d.nope + d.v) + Hw * d.v * D
    per_token = (L * attn + L_dense * 3 * D * p.win["ff"]
                 + L_moe * (D * d.n_router + 3 * D * p.win["sff"]) + D * V)
    S, B = mix["seq_len"], mix["seqs_per_step"]
    T = B * S
    routed_rows = T * d.top_k * p.win["experts"] / d.n_router
    routed = L_moe * routed_rows * 3 * D * p.win["eff"]
    # causal attention: QK^T over the qk head dim, PV over the v head dim
    attn_fwd = 2 * (qk + d.v) * Hw * causal_pairs(S)
    step = 6 * (per_token * T + routed) + 3 * L * B * attn_fwd
    return float(mix["clients"] * mix["local_steps"] * step)


# -- the round ---------------------------------------------------------------


class Reference:
    """Jitted reference rounds of one configuration and traffic mix."""

    def __init__(self, config: dict, mix: dict, dtype=jnp.float32,
                 devices=None, precision: str = "highest"):
        self.d = dims(config)
        self.p = plan(config, mix["capacity"])
        self.mix = mix
        self.dtype = dtype
        self.precision = precision
        self.devices = list(devices or [])
        self._client = jax.jit(self._client_delta)
        self._add = jax.jit(lambda acc, d, o: add_scattered(acc, d, o,
                                                            self.p))
        self._apply = jax.jit(self._apply_mean)

    def _client_delta(self, params, tokens, offs):
        """K SGD steps of one client on its window: (f32 delta, losses)."""
        sub0 = extract(params, offs, self.p)
        sub, losses = sub0, []
        grad = jax.value_and_grad(loss)
        for k in range(tokens.shape[0]):
            value, g = grad(sub, tokens[k], offs["experts"], self.d)
            sub = jax.tree_util.tree_map(
                lambda w, gw: w - self.mix["client_lr"] * gw, sub, g)
            losses.append(value)
        delta = jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            sub, sub0)
        return delta, jnp.stack(losses).astype(jnp.float32)

    def _apply_mean(self, params, acc):
        lr, C = self.mix.get("server_lr", 1.0), self.mix["clients"]
        return jax.tree_util.tree_map(
            lambda w, a: (w.astype(jnp.float32) + lr * a / C).astype(w.dtype),
            params, acc)

    def cast(self, params):
        return jax.tree_util.tree_map(lambda x: x.astype(self.dtype), params)

    def round(self, params, tokens, round_idx, clients=None):
        """One round on ``tokens [K, C, B, S]``: ``(params, losses [K, C])``.
        ``clients`` limits the exchange to those clients' deltas (the
        server still divides by C).  Client c runs on device c mod n."""
        C = self.mix["clients"]
        offs = offsets(self.p, 0, round_idx, C,
                       self.mix.get("stagger", False))
        home = self.devices[0] if self.devices else None
        acc = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params)
        losses = {}
        with jax.default_matmul_precision(self.precision):
            for c in range(C):
                d = self.devices[c % len(self.devices)] if self.devices \
                    else None
                p = params if d is None or d == home \
                    else jax.device_put(params, d)
                tok = jax.device_put(np.asarray(tokens[:, c]), d)
                oc = {k: int(v[c]) for k, v in offs.items()}
                delta, lc = self._client(p, tok, oc)
                losses[c] = jax.device_put(lc, home)
                if clients is None or c in clients:
                    acc = self._add(acc, jax.device_put(delta, home), oc)
            params = self._apply(params, acc)
        return params, jnp.stack([losses[c] for c in range(C)], axis=1)
