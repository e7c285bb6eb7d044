"""Operations and bytes of a round, counted from a cell's shapes.

``model_flops``: what the sub-model needs per round, forward and backward,
as the configuration's own plain reference counts it
(``model_flops(config, mix)`` of ``bench/reference/<config["reference"]>.py``),
so a configuration with another block brings its count with its reference.

``rolling_matmul_calls``: the calls routed through ``kernels.dispatch``'s
rolling-matmul family per round, as they run: q/k/v projections (one
kernel each) and the gate/up pair (one two-weight kernel), each forward
twice (the forward pass and its recompute under remat), and one backward
kernel for dx each.  ``w_o``, ``w_down``, dW and the head are XLA dots and
are not counted.  Bytes are the least the call must move: its activation
and window of weights read once, its output written once.  Where every
client shares one window, the clients' rows fold into one call that reads
the window once; per-client windows read one window per client.
"""
from __future__ import annotations

from bench import spec
from bench.reference.round import sizes

F32 = 4


def model_flops(config: dict, mix: dict) -> float:
    """FLOPs the sub-model needs per round, summed over clients and local
    steps, by the configuration's reference."""
    return spec.reference_module(config).model_flops(config, mix)


def _mm(M, K, N, weights=1, readers=1):
    """(flops, bytes) of ``weights`` matmuls x[M, K] @ W[K, N] sharing x;
    ``readers`` separate windows of W are read."""
    return (2.0 * M * K * N * weights,
            F32 * (M * K + readers * weights * K * N + weights * M * N))


def rolling_matmul_calls(config: dict, mix: dict):
    """``[(name, flops, bytes, calls per round)]``."""
    z = sizes(config, mix)
    shared = not mix.get("stagger", False)
    T = z["B"] * z["S"]
    M = z["C"] * T
    readers = 1 if shared else z["C"]
    n = z["L"] * z["K"]
    out = []
    for name, N, w in (("q", z["nq"], 1), ("k", z["nkv"], 1),
                       ("v", z["nkv"], 1), ("gate_up", z["F"], 2)):
        fl, by = _mm(M, z["D"], N, w, readers)
        out.append((name + ".fwd", fl, by, 2 * n))
        # dx = dy[M, N] @ W^T: contraction over the window, summed over w
        dfl = 2.0 * M * N * z["D"] * w
        dby = F32 * (w * M * N + readers * w * z["D"] * N + M * z["D"])
        out.append((name + ".dx", dfl, dby, n))
    return out


def rolling_matmul_least_s(config: dict, mix: dict, peak_flops: float,
                           hbm_bw: float) -> float:
    """Least device time of one round's rolling-matmul calls: per call the
    larger of its FLOPs at peak and its bytes at full bandwidth."""
    return sum(calls * max(fl / peak_flops, by / hbm_bw)
               for _, fl, by, calls in rolling_matmul_calls(config, mix))
