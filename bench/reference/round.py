"""Plain reference of one federated sub-model round (rolling windows).

Per round: choose the windows, then for every client extract its sub-model
(a contiguous window of the MLP width and of the attention heads), run K
plain SGD steps on it, and take the window delta; the server adds the mean
of the deltas, each scattered back to its window, times the server rate.

Rolling windows (the paper's Algorithm 2): an axis of size n with windows
of size w has R = ceil(n / w) windows at offsets round(i (n - w) / (R - 1));
each epoch of R rounds follows a fresh permutation of them, drawn from the
window seed, and with ``stagger`` client c takes the window c places on.
The MLP width and the key/value heads are primary axes that share the
permutation; query heads follow the key/value heads by the group size.

Runs in float32 under the highest matmul precision; ``dtype`` runs the
whole round in another type instead (the control), and ``precision``
takes another matmul precision (``"bfloat16"``: one bfloat16 pass of the
operands, float32 accumulation, on a TPU).

``model_flops`` counts what the round needs, for ``bench.flops`` and the
``round_mfu_pct`` reader: 2 FLOPs per multiply-add, backward twice the
forward, every windowed projection, the LM head and causal attention over
the window's heads; rematerialised recompute is not counted.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import model


@dataclass(frozen=True)
class Plan:
    """Static window sizes of one configuration at one capacity."""

    d_ff: int
    ff_win: int
    kv: int
    kv_win: int
    group: int           # query heads per key/value head
    n_windows: int


def plan(config: dict, capacity: float) -> Plan:
    F, KV = config["intermediate_size"], config["num_key_value_heads"]
    ff_win = max(1, int(round(capacity * F)))
    kv_win = max(1, int(round(capacity * KV)))
    R = max(math.ceil(F / ff_win), math.ceil(KV / kv_win))
    return Plan(F, ff_win, KV, kv_win,
                config["num_attention_heads"] // KV, R)


def sizes(config: dict, mix: dict) -> dict:
    """The sizes of one round that the counts take: model widths, the
    window's query/key-value columns and MLP width, and the mix's job."""
    p = plan(config, mix["capacity"])
    hd = config["head_dim"]
    return dict(D=config["hidden_size"], V=config["vocab_size"],
                L=config["num_hidden_layers"], hd=hd,
                nq=p.kv_win * p.group * hd, nkv=p.kv_win * hd,
                hq=p.kv_win * p.group, F=p.ff_win,
                S=mix["seq_len"], B=mix["seqs_per_step"],
                C=mix["clients"], K=mix["local_steps"],
                window=config.get("sliding_window") or 0)


def causal_pairs(S: int, window: int = 0) -> int:
    """(query, key) pairs a causal (optionally sliding) mask keeps."""
    w = window or S
    return sum(min(i + 1, w) for i in range(S))


def model_flops(config: dict, mix: dict) -> float:
    """FLOPs the sub-model needs per round, summed over clients and local
    steps."""
    z = sizes(config, mix)
    per_layer = (z["D"] * z["nq"] * 2 + z["D"] * z["nkv"] * 2
                 + 3 * z["D"] * z["F"])
    params = z["L"] * per_layer + z["D"] * z["V"]
    tokens = z["B"] * z["S"]
    # causal attention: QK^T and PV, 2 FLOPs per pair and head dim each
    attn_fwd = 4 * z["hd"] * z["hq"] * causal_pairs(z["S"], z["window"])
    step = 6 * params * tokens + 3 * z["L"] * z["B"] * attn_fwd
    return float(z["C"] * z["K"] * step)


def _grid(n, w, R):
    if R == 1 or n == w:
        return np.zeros(R, np.int32)
    own = math.ceil(n / w)
    g = [int(round(i * (n - w) / (own - 1))) for i in range(own)]
    return np.resize(np.asarray(g, np.int32), R)


def offsets(p: Plan, window_seed: int, round_idx: int, clients: int,
            stagger: bool):
    """``(ff_offsets [C], kv_offsets [C])`` of round ``round_idx``."""
    R = p.n_windows
    e, r = divmod(round_idx, R)
    perm = np.asarray(jax.random.permutation(
        jax.random.fold_in(jax.random.PRNGKey(window_seed), e), R))
    idx = perm[(r + np.arange(clients)) % R] if stagger \
        else np.full(clients, perm[r])
    return _grid(p.d_ff, p.ff_win, R)[idx], _grid(p.kv, p.kv_win, R)[idx]


def _window(path, p: Plan):
    """``[(dim, window size, 'ff' | 'kv' | 'q')]`` of a stacked leaf."""
    name = path[-1]
    if name in ("w_gate", "w_up"):
        return [(2, p.ff_win, "ff")]
    if name == "w_down":
        return [(1, p.ff_win, "ff")]
    if name in ("wk", "wv"):
        return [(2, p.kv_win, "kv")]
    if name == "wq":
        return [(2, p.kv_win * p.group, "q")]
    if name == "wo":
        return [(1, p.kv_win * p.group, "q")]
    return []


def _start(kind, off_ff, off_kv, p: Plan):
    return {"ff": off_ff, "kv": off_kv, "q": off_kv * p.group}[kind]


def _map(fn, tree, path=()):
    if isinstance(tree, dict):
        return {k: _map(fn, v, path + (k,)) for k, v in tree.items()}
    return fn(path, tree)


def extract(params, off_ff, off_kv, p: Plan):
    def leaf(path, x):
        for dim, w, kind in _window(path, p):
            x = jax.lax.dynamic_slice_in_dim(
                x, _start(kind, off_ff, off_kv, p), w, axis=dim)
        return x
    return _map(leaf, params)


def add_scattered(acc, delta, off_ff, off_kv, p: Plan):
    """``acc`` plus ``delta`` (sub-model shaped) placed in its window."""
    def leaf(path, a):
        d = _get(delta, path)
        starts = [0] * a.ndim
        for dim, _, kind in _window(path, p):
            starts[dim] = _start(kind, off_ff, off_kv, p)
        cur = jax.lax.dynamic_slice(a, starts, d.shape)
        return jax.lax.dynamic_update_slice(a, cur + d, starts)
    return _map(leaf, acc)


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


class Reference:
    """Jitted reference rounds of one configuration and traffic mix."""

    def __init__(self, config: dict, mix: dict, dtype=jnp.float32,
                 devices=None, precision: str = "highest"):
        self.d = model.dims(config)
        self.p = plan(config, mix["capacity"])
        self.mix = mix
        self.dtype = dtype
        self.precision = precision
        self.devices = list(devices or [])
        self._client = jax.jit(self._client_delta)
        self._add = jax.jit(lambda acc, d, f, k: add_scattered(
            acc, d, f, k, self.p))
        self._apply = jax.jit(self._apply_mean)

    def _client_delta(self, params, tokens, off_ff, off_kv):
        """K SGD steps of one client on its window: (f32 delta, losses)."""
        sub0 = extract(params, off_ff, off_kv, self.p)
        sub, losses = sub0, []
        grad = jax.value_and_grad(model.loss)
        for k in range(tokens.shape[0]):
            value, g = grad(sub, tokens[k], self.d)
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
        server still divides by C): the fault of a lost exchange.  With
        several ``devices`` client c runs on device c mod n, each device on
        a copy of the parameters; the server's sum runs on the first."""
        C = self.mix["clients"]
        offs = offsets(self.p, 0, round_idx, C,
                       self.mix.get("stagger", False))
        home = self.devices[0] if self.devices else None
        acc = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params)
        copies, pending, losses = {}, {}, {}

        def add(c, delta, lc):
            losses[c] = jax.device_put(lc, home)
            if clients is None or c in clients:
                return self._add(acc, jax.device_put(delta, home),
                                 offs[0][c], offs[1][c])
            return acc

        with jax.default_matmul_precision(self.precision):
            for c in range(C):
                d = self.devices[c % len(self.devices)] if self.devices \
                    else None
                if d is not None and d != home and d not in copies:
                    copies[d] = jax.device_put(params, d)
                p = copies.get(d, params)
                tok = jax.device_put(np.asarray(tokens[:, c]), d)
                delta, lc = self._client(p, tok, offs[0][c], offs[1][c])
                if d is None or d == home:
                    acc = add(c, delta, lc)
                else:
                    pending[c] = (delta, lc)
            copies.clear()
            for c in sorted(pending):
                acc = add(c, *pending.pop(c))
            params = self._apply(params, acc)
        return params, jnp.stack([losses[c] for c in range(C)], axis=1)
