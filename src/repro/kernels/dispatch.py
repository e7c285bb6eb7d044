"""Kernel backend dispatch: ``pallas`` | ``jnp`` | ``auto``.

The fed-round hot paths (client masked SGD, server fill-in average, window
matmuls) have two interchangeable arms:

* **pallas** — the fused TPU kernels in this package (compiled on TPU;
  interpret mode elsewhere, which is an emulation for testing, never a win);
* **jnp**    — the pure-jnp oracles (``repro.kernels.ref`` /
  ``repro.core.submodel``), which XLA handles well on CPU/GPU.

``auto`` (the default, overridable via the ``REPRO_KERNEL_BACKEND`` env var)
picks the Pallas arm only where it actually wins: compiled on a real TPU
backend; the jnp oracle everywhere else.  Every dispatched op is
tolerance-tested against its oracle arm in ``tests/test_dispatch.py``, and
``benchmarks/run.py --only fed_round_pallas`` compares full rounds end to
end.

All ops accept ``backend=None`` (resolve from env) or an explicit member of
``BACKENDS``; resolution happens at trace time so a jitted fed round bakes
in one arm.
"""
from __future__ import annotations

import collections
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import custom_batching

from repro.core import submodel as sm
from repro.kernels import ref
from repro.kernels.ops import fillin_agg_tree, masked_sgd_tree
from repro.kernels.rolling_matmul import LAUNCHES, tile_vmem_bytes
from repro.kernels.rolling_matmul import rolling_matmul as _rolling_mm_pallas
from repro.kernels.rolling_matmul import \
    rolling_matmul_multi as _rolling_mm_multi_pallas
from repro.kernels.rolling_matmul_batched import \
    rolling_matmul_batched as _rolling_mm_batched_pallas
from repro.kernels.rolling_matmul_batched import \
    rolling_matmul_batched_dx as _rolling_dx_batched_pallas
from repro.kernels.rolling_matmul_batched import \
    rolling_matmul_batched_dx_multi as _rolling_dx_batched_multi_pallas
from repro.kernels.rolling_matmul_batched import \
    rolling_matmul_batched_multi as _rolling_mm_batched_multi_pallas
from repro.kernels.rolling_matmul_bwd import \
    rolling_matmul_dx as _rolling_dx_pallas
from repro.kernels.rolling_matmul_bwd import \
    rolling_matmul_dx_multi as _rolling_dx_multi_pallas

BACKENDS = ("pallas", "jnp", "auto")
BACKEND_ENV = "REPRO_KERNEL_BACKEND"


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Pallas must run in interpret mode off-TPU (Mosaic needs a TPU)."""
    return not on_tpu()


def resolve_backend(backend: str | None = None) -> str:
    """Resolve ``backend`` (or the env default) to a concrete arm."""
    backend = backend or os.environ.get(BACKEND_ENV, "auto")
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {backend!r}; expected one of {BACKENDS}")
    if backend == "auto":
        return "pallas" if on_tpu() else "jnp"
    return backend


#: Rolling-matmul calls that resolved to the ``pallas`` arm but ran the jnp
#: oracle, keyed by op name.  A shape the kernel grid cannot tile, or a
#: traced offset without an alignment certificate, takes the oracle; on a
#: TPU that is XLA code in place of the kernel the caller asked for.
#: Counted at trace time (a jitted round counts once per trace), so a run
#: that needs the kernels clears this before tracing and requires it to
#: stay empty (``chip_smoke.py``).
ORACLE_FALLBACKS: collections.Counter = collections.Counter()


def _takes_pallas(backend, tileable: bool, op: str) -> bool:
    """True when ``op`` runs its Pallas arm; a pallas-resolved call that
    cannot is counted in :data:`ORACLE_FALLBACKS`."""
    if resolve_backend(backend) != "pallas":
        return False
    if not tileable:
        ORACLE_FALLBACKS[op] += 1
    return tileable


# ---------------------------------------------------------------------------
# Block-size autotuning (deterministic; no on-device timing)
# ---------------------------------------------------------------------------

#: Cache of autotuned (bm, bn, bk) triples, keyed per
#: ((M, K, win), dtype-name, resolved-backend, role).  Deterministic — the
#: tuner never times anything — so the cache is a memo, not a measurement
#: store, and two processes always agree on the choice for a key.
_AUTOTUNE_CACHE: dict = {}

#: Process-wide override installed by :func:`set_block_override`
#: (``--kernel-block`` in ``launch/train.py``).  Wins over the autotuner for
#: every op whose block args were left at ``None``; explicit per-call block
#: args still take precedence.  Never written into ``_AUTOTUNE_CACHE``.
_BLOCK_OVERRIDE: tuple | None = None

#: Largest window-side block edge — one 128-lane MXU tile.  The kernels
#: count the window offset in blocks of this edge (``off_blocks = offset //
#: block``), and the models' alignment certificate
#: (``AxisWindow.aligned(min(128, win))``) vouches for exactly that grid,
#: so this edge never grows: a coarser one would floor-round certified
#: offsets to the wrong block.
_BLOCK_CAP = 128

#: Working-set budget of one rolling-matmul call: its operand and output
#: blocks, double-buffered, plus the f32 accumulator
#: (``rolling_matmul.tile_vmem_bytes``).  A v5e core has 128 MiB of VMEM;
#: the budget plus the headroom each call adds for Mosaic's own scratch
#: (``rolling_matmul.vmem_limit_bytes``) stays under half of it, so a
#: kernel's limit never crowds the scoped buffers XLA's fusions take.
_VMEM_BUDGET_BYTES = 40 * 2**20

#: Which edge of each role carries the window offset: the forward's output
#: columns ``bn``; dx's contraction ``bk`` (its output ``bn`` runs over K).
ROLES = ("fwd", "dx")


def _choose_block(dim: int, cap: int = _BLOCK_CAP) -> int:
    """Largest divisor of ``dim`` that is ≤ ``cap``, preferring multiples of
    8 (f32 sublane width) over raw size.  Divisors-only keeps every Pallas
    grid exact — the kernels assert ``dim % block == 0`` — so no block
    drops or repeats a row or column.  The choice is not free of numerics:
    a contraction edge sets how the f32 partial sums over K are grouped."""
    dim = int(dim)
    if dim <= 0:
        return 1
    divisors = [d for d in range(1, min(dim, cap) + 1) if dim % d == 0]
    sublane = [d for d in divisors if d % 8 == 0]
    return max(sublane) if sublane else max(divisors)


def _edge_candidates(dim: int, align: int) -> set:
    """Block edges the tuner may give an offset-free edge of ``dim``: the
    ≤ 128 choice of :func:`_choose_block`, and every divisor above 128 that
    Mosaic's tiling rule admits — the whole dim or a multiple of ``align``
    (8·4/itemsize rows, 128 lanes)."""
    return {_choose_block(dim)} | {
        d for d in range(_BLOCK_CAP + 1, dim + 1)
        if dim % d == 0 and (d == dim or d % align == 0)}


def _tune(M, K, win, itemsize, role):
    """(bm, bn, bk) for one role: the window edge at :func:`_choose_block`
    (≤ 128), the two offset-free edges the pair whose call streams the
    fewest bytes from HBM inside the VMEM budget (fewer grid steps, then
    the larger ``bm``, break ties).  Forward, ``x[M, K] @ W[K, win]``: W's
    window is read once per row block and x once per window block, unless
    ``bk == K`` keeps x's block index fixed across the window sweep.  dx,
    ``dy[M, win] @ W[K, win]^T``: W's window is read once per row block, dy
    once per output column block.  The ≤ 128 pair always fits, so some pair
    does."""
    wb = _choose_block(win)
    rows = _edge_candidates(M, 32 // itemsize)
    cols = _edge_candidates(K, 128)
    if role == "fwd":
        def cost(bm, bk):
            x_reads = 1 if bk == K else win // wb
            return ((M // bm) * K * win + x_reads * M * K,
                    (M // bm) * (win // wb) * (K // bk))

        def triple(bm, e):
            return bm, wb, e
    else:
        def cost(bm, bn):
            return ((M // bm) * K * win + (K // bn) * M * win,
                    (M // bm) * (K // bn) * (win // wb))

        def triple(bm, e):
            return bm, e, wb
    fits = [(bm, e) for bm in rows for e in cols
            if tile_vmem_bytes(*triple(bm, e), itemsize)
            <= _VMEM_BUDGET_BYTES]
    return triple(*min(fits, key=lambda p: (*cost(*p), -p[0])))


def autotune_blocks(M, K, win, dtype=jnp.float32, backend=None, role="fwd"):
    """Pick (bm, bn, bk) for one role of a rolling matmul of ``x[M, K] @
    W[K, off:off+win]`` — deterministically, from the divisors of the
    operand dims and the VMEM budget.

    ``role="fwd"``: ``bn`` is the window edge (the offset's block), ``bm``
    and ``bk`` grow; ``role="dx"``: ``bk`` is the window edge, ``bm`` and
    ``bn`` (over K) grow.  The window edge stays at :func:`_choose_block`
    (≤ 128: the block the alignment certificate vouches for); see
    :func:`_tune` for the offset-free edges.  The budget counts elements
    by ``dtype``, so bfloat16 gets twice as many.

    Cached per ``((M, K, win), dtype, resolved backend, role)``; the
    backend is in the key because the jnp arm ignores blocks while future
    TPU generations may want different budgets, and crossing keys would
    let one shape's choice leak into another's.  Call
    :func:`clear_block_cache` to drop the memo (tests),
    :func:`set_block_override` to bypass the tuner entirely.
    """
    if role not in ROLES:
        raise ValueError(f"unknown block role {role!r}; expected {ROLES}")
    key = ((int(M), int(K), int(win)), np.dtype(dtype).name,
           resolve_backend(backend), role)
    hit = _AUTOTUNE_CACHE.get(key)
    if hit is not None:
        return hit
    choice = _tune(int(M), int(K), int(win), np.dtype(dtype).itemsize, role)
    _AUTOTUNE_CACHE[key] = choice
    return choice


def set_block_override(blocks):
    """Install a process-wide (bm, bn, bk) override, or ``None`` to clear.

    The override wins over the autotuner for every dispatched rolling-matmul
    whose block args default to ``None``; explicit per-call ``bm/bn/bk``
    still take precedence.  Like explicit args it is one triple in the
    forward's roles, which dx reads with ``bn`` over K and ``bk`` over the
    window.  It is never written into the autotune cache, so clearing it
    restores tuned behaviour without a cache flush."""
    global _BLOCK_OVERRIDE
    if blocks is not None:
        bm, bn, bk = (int(b) for b in blocks)
        if min(bm, bn, bk) < 1:
            raise ValueError(f"block sizes must be >= 1, got {blocks!r}")
        blocks = (bm, bn, bk)
    _BLOCK_OVERRIDE = blocks
    return blocks


def clear_block_cache():
    """Drop all memoized autotune choices and the record of traced
    launches that :func:`block_choices` reads (test isolation)."""
    _AUTOTUNE_CACHE.clear()
    LAUNCHES.clear()


def block_choices():
    """The blocks every rolling-matmul kernel was traced with in this
    process: one entry per (kernel, operand shapes) with its ``blocks``
    ``(bm, bn, bk)``, ``grid_steps`` a call and ``vmem_limit_bytes``,
    sorted by kernel name.  Written at trace time, so reading it costs a
    round nothing.  A kernel under a custom batching rule is also traced
    unbatched (for its output type), so that form is listed beside the
    batched one that runs."""
    return [dict(op=name, x=list(a), w=list(w), out=list(out),
                 blocks=list(v["blocks"]), grid_steps=v["grid_steps"],
                 vmem_limit_bytes=v["vmem_limit_bytes"])
            for (name, a, w, out), v in sorted(LAUNCHES.items())]


def _resolve_blocks(M, K, win, dtype, backend, bm, bn, bk, role="fwd"):
    """Fill ``None`` block args for ``role``: explicit call args >
    ``set_block_override`` > cached :func:`autotune_blocks` choice."""
    if bm is not None and bn is not None and bk is not None:
        return bm, bn, bk
    if _BLOCK_OVERRIDE is not None:
        abm, abn, abk = _BLOCK_OVERRIDE
    else:
        abm, abn, abk = autotune_blocks(M, K, win, dtype, backend, role)
    return (abm if bm is None else bm,
            abn if bn is None else bn,
            abk if bk is None else bk)


def _role_blocks(M, K, win, dtype, backend, bm, bn, bk):
    """``(forward, dx)`` block triples of one dispatched call."""
    return tuple(_resolve_blocks(M, K, win, dtype, backend, bm, bn, bk, r)
                 for r in ROLES)


# ---------------------------------------------------------------------------
# Elementwise fed-round ops (tree-level; leaves may carry leading client dims)
# ---------------------------------------------------------------------------


def masked_sgd(params, masks, grads, lr, backend=None):
    """w ← w − η·(m ⊙ g) over a pytree.  The op is elementwise, so leaves may
    carry any leading (client) axes; the pallas arm flattens them into the
    rows×128-lane kernel layout."""
    if resolve_backend(backend) == "jnp":
        return sm.masked_sgd_step(params, masks, grads, lr)
    return masked_sgd_tree(params, masks, grads, lr,
                           interpret=interpret_mode())


def sgd_step(params, grads, lr, backend=None):
    """Unmasked client update w ← w − η·g (window mode trains compact
    sub-models, so no mask exists), in f32 and cast back to each leaf's
    dtype.

    One arm on every backend: XLA's own elementwise fusion on each leaf as
    it lies.  A Pallas call would need the leaf flattened and padded into
    its rows × lanes layout, which on a TPU is a physical relayout of the
    whole carried tree (and a custom call forces the default layout on
    the rest).  ``backend`` is kept so the client optimizers that route
    through here keep one signature."""
    del backend

    def leaf(p, g):
        return (p.astype(jnp.float32)
                - lr * g.astype(jnp.float32)).astype(p.dtype)

    return jax.tree_util.tree_map(leaf, params, grads)


def fillin_agg(server, client_params, client_masks, server_lr=1.0,
               backend=None):
    """Server fill-in average (delta form): w ← w + (s/C)·Σ_c m_c ⊙ (w_c − w).

    ``client_params`` / ``client_masks`` leaves are stacked on a leading
    client axis.  ``server_lr=1`` is the paper's plain average."""
    if resolve_backend(backend) == "jnp":
        if server_lr == 1.0:
            return sm.fillin_average(server, client_params, client_masks)
        # delta in f32 (not the param dtype): bf16 subtraction would round
        # the client deltas — mirror sm.fillin_average / the Pallas arm.
        return jax.tree_util.tree_map(
            lambda w, ws, ms: (w.astype(jnp.float32) + server_lr
                               * (ms.astype(jnp.float32)
                                  * (ws.astype(jnp.float32)
                                     - w[None].astype(jnp.float32))).mean(0)
                               ).astype(w.dtype),
            server, client_params, client_masks)
    return fillin_agg_tree(server, client_params, client_masks,
                           server_lr=server_lr, interpret=interpret_mode())


# ---------------------------------------------------------------------------
# Window matmul (the sub-model compute hot spot)
# ---------------------------------------------------------------------------


def _offset_aligned(offset, block, assume_aligned):
    """True when ``offset`` provably lands on a block boundary.  The kernels
    floor-round the offset to a block multiple (``off_blocks = offset //
    block``), so a misaligned offset would be silently wrong, not an error."""
    try:
        return int(offset) % block == 0
    except (TypeError, jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError):
        # Traced offset: alignment is unknowable here.  Only take the fused
        # arm when the caller vouches for it (window scheme offsets all
        # multiples of the block width); otherwise the oracle arm is the
        # safe default.
        return assume_aligned


#: Lanes of one vreg: Mosaic takes a block whose last (lane) dim is a
#: multiple of this or the array's whole dim, and refuses any other.
_LANES = 128


def _lane_block_ok(block: int, full: int) -> bool:
    """True when a window-edge block of ``block`` lanes over a weight whose
    windowed dim is ``full`` wide meets Mosaic's tiling rule.  The window
    edge is the lane dim of the weight's block, of the forward's output
    and of dx's incoming cotangent; a window whose block is neither a
    multiple of 128 nor the whole dim (a 5472- or 704-wide window takes
    96 or 88, a window narrower than 128 all of itself) never reaches
    Mosaic, which interpret mode would not catch."""
    return block % _LANES == 0 or block == full


def _rolling_tileable(M, K, N, win, offset, bm, bn, bk, assume_aligned):
    """Static check that the forward Pallas grid divides evenly, its
    window block is a lane tile Mosaic takes, and the offset lands on a
    ``bn`` (output-column) block boundary."""
    bm, bn, bk = min(bm, M), min(bn, win), min(bk, K)
    if M % bm or win % bn or K % bk or not _lane_block_ok(bn, N):
        return False
    return _offset_aligned(offset, bn, assume_aligned)


def _pallas_fwd(x, w, offset, win, bm, bn, bk):
    """The Pallas forward arm, batchable: under ``jax.vmap`` (the fused
    client phase maps the model over clients) this lowers to ONE
    batched-offset kernel call (``kernels.rolling_matmul_batched``) instead
    of the per-client loop the generic pallas_call batching rule would
    synthesize — each client's grid row prefetches its own offset."""
    interp = interpret_mode()

    @custom_batching.custom_vmap
    def fwd(x, w, offset):
        return _rolling_mm_pallas(x, w, offset, win, bm=bm, bn=bn, bk=bk,
                                  interpret=interp)

    @fwd.def_vmap
    def _rule(axis_size, in_batched, x, w, offset):  # noqa: ANN001
        xb, wb, ob = in_batched
        if not wb and not ob:
            # shared weight AND offset: fold the batch into rows — the
            # unbatched kernel already expresses this with zero copies.
            # bm is clamped to the UNBATCHED row count so the folded rows
            # (axis_size * M) still tile evenly.
            y = _rolling_mm_pallas(x.reshape(-1, x.shape[-1]), w, offset,
                                   win, bm=min(bm, x.shape[-2]), bn=bn,
                                   bk=bk, interpret=interp)
            return y.reshape(axis_size, -1, win), True
        xx = x if xb else jnp.broadcast_to(x[None], (axis_size,) + x.shape)
        ww = w if wb else jnp.broadcast_to(w[None], (axis_size,) + w.shape)
        oo = jnp.asarray(offset, jnp.int32)
        if not ob:
            oo = jnp.broadcast_to(oo[None], (axis_size,))
        y = _rolling_mm_batched_pallas(xx, ww, oo, win, bm=bm, bn=bn, bk=bk,
                                       interpret=interp)
        return y, True

    return fwd(x, w, jnp.asarray(offset, jnp.int32))


def _rolling_fwd_arm(x, w, offset, win, backend, bm, bn, bk, assume_aligned):
    M, K = x.shape
    if _takes_pallas(backend,
                     _rolling_tileable(M, K, w.shape[-1], win, offset, bm,
                                       bn, bk, assume_aligned),
                     "rolling_matmul"):
        return _pallas_fwd(x, w, offset, win, bm, bn, bk)
    return ref.rolling_matmul_ref(x, w, offset, win)


def _pallas_dx(dy, w, offset, win, bm, bn, bk):
    """Batchable Pallas backward arm (mirrors :func:`_pallas_fwd`)."""
    interp = interpret_mode()

    @custom_batching.custom_vmap
    def bwd(dy, w, offset):
        return _rolling_dx_pallas(dy, w, offset, win, bm=bm, bn=bn, bk=bk,
                                  interpret=interp)

    @bwd.def_vmap
    def _rule(axis_size, in_batched, dy, w, offset):  # noqa: ANN001
        dyb, wb, ob = in_batched
        if not wb and not ob:
            dx = _rolling_dx_pallas(dy.reshape(-1, win), w, offset, win,
                                    bm=min(bm, dy.shape[-2]), bn=bn, bk=bk,
                                    interpret=interp)
            return dx.reshape(axis_size, -1, w.shape[0]), True
        dd = dy if dyb else jnp.broadcast_to(dy[None],
                                             (axis_size,) + dy.shape)
        ww = w if wb else jnp.broadcast_to(w[None], (axis_size,) + w.shape)
        oo = jnp.asarray(offset, jnp.int32)
        if not ob:
            oo = jnp.broadcast_to(oo[None], (axis_size,))
        dx = _rolling_dx_batched_pallas(dd, ww, oo, win, bm=bm, bn=bn,
                                        bk=bk, interpret=interp)
        return dx, True

    return bwd(dy, w, jnp.asarray(offset, jnp.int32))


def _rolling_dx_arm(dy, w, offset, win, backend, bm, bn, bk, assume_aligned):
    """dx = dy @ w[:, offset:offset+win]^T — second offset-prefetch kernel
    (the contraction runs over the window, so the offset must land on a
    ``bk`` block boundary); jnp oracle otherwise."""
    M = dy.shape[0]
    K = w.shape[0]
    bm_, bn_, bk_ = min(bm, M), min(bn, K), min(bk, win)
    tileable = (M % bm_ == 0 and K % bn_ == 0 and win % bk_ == 0
                and _lane_block_ok(bk_, w.shape[-1])
                and _offset_aligned(offset, bk_, assume_aligned))
    if _takes_pallas(backend, tileable, "rolling_matmul_dx"):
        return _pallas_dx(dy, w, offset, win, bm, bn, bk)
    wsub = jax.lax.dynamic_slice_in_dim(w, offset, win, axis=1)
    return jax.lax.dot_general(
        dy, wsub, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dy.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _rolling_mm(x, w, offset, win, backend, blocks, assume_aligned):
    return _rolling_fwd_arm(x, w, offset, win, backend, *blocks[0],
                            assume_aligned)


def _rolling_mm_fwd(x, w, offset, win, backend, blocks, assume_aligned):
    y = _rolling_fwd_arm(x, w, offset, win, backend, *blocks[0],
                         assume_aligned)
    return y, (x, w, offset)


def _rolling_mm_bwd(win, backend, blocks, assume_aligned, res, dy):
    """Custom VJP: dx through the offset-prefetch backward kernel (oracle
    fallback), dW as a window scatter-add — exactly the transpose autodiff
    derives for the slice-then-matmul oracle, so grads through the fused
    arm match grads through extract-then-matmul."""
    x, w, offset = res
    dx = _rolling_dx_arm(dy, w, offset, win, backend, *blocks[1],
                         assume_aligned)
    dw_win = jax.lax.dot_general(
        x, dy, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(w.dtype)
    dw = jax.lax.dynamic_update_slice(
        jnp.zeros(w.shape, dw_win.dtype), dw_win, (0, offset))
    d_off = np.zeros(np.shape(offset), jax.dtypes.float0)
    return dx, dw, d_off


_rolling_mm.defvjp(_rolling_mm_fwd, _rolling_mm_bwd)


def rolling_matmul(x, w, offset, win, backend=None, bm=None, bn=None,
                   bk=None, assume_aligned=False):
    """y[M, win] = x[M, K] @ w[K, offset : offset+win], differentiable.

    Block sizes default to ``None`` = resolved at trace time via
    :func:`autotune_blocks` (explicit args > :func:`set_block_override` >
    cached autotune choice), one triple per role: the forward and dx
    kernels each get blocks sized for the edge that carries their offset.

    Pallas arm fuses the window into the matmul's index_map so inactive
    columns of ``w`` are never read from HBM; jnp arm is the dynamic-slice
    oracle.  Falls back to the oracle for shapes the MXU grid cannot tile,
    and — because the kernels floor-round the offset to a block boundary —
    for *traced* offsets unless ``assume_aligned=True`` (pass it when every
    offset the scheme can produce is a multiple of the block width, cf.
    ``WindowScheme.grid_multiple`` / ``AxisWindow.aligned``).  Each such
    fallback under the ``pallas`` arm is counted in
    :data:`ORACLE_FALLBACKS`.

    Registered with a custom VJP: ``dx = dy @ w[:, off:off+win]^T`` via the
    offset-prefetch backward kernel (``kernels.rolling_matmul_bwd``), ``dW``
    as a window scatter-add of ``x^T @ dy``; both halves dispatch per
    backend with the jnp oracle as the autodiff fallback.

    Under ``jax.vmap`` with a *batched* offset (the staggered fused client
    phase: per-client windows), both Pallas halves lower to the
    batched-offset kernels in ``kernels.rolling_matmul_batched`` — one grid
    row per batch element, each prefetching its own offset — instead of a
    synthesized per-element loop; the jnp oracle batches through the
    ordinary gather rules.  :func:`rolling_matmul_batched` is the same arm
    with the batch explicit in the call."""
    blocks = _role_blocks(x.shape[-2], x.shape[-1], win, x.dtype, backend,
                          bm, bn, bk)
    return _rolling_mm(x, w, offset, win, backend, blocks, assume_aligned)


# -- explicit batched-offset form (per-client windows, staggered schemes) ----


def _batched_offsets_aligned(offsets, block, assume_aligned):
    """Concrete offsets: every row must land on a block boundary; traced
    offsets fall back to the caller's alignment certificate."""
    try:
        return bool((np.asarray(offsets) % block == 0).all())
    except (TypeError, jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError):
        return assume_aligned


def _rolling_b_fwd_arm(x, w, offsets, win, backend, bm, bn, bk,
                       assume_aligned):
    _, M, K = x.shape
    bm_, bn_, bk_ = min(bm, M), min(bn, win), min(bk, K)
    tileable = (M % bm_ == 0 and win % bn_ == 0 and K % bk_ == 0
                and _lane_block_ok(bn_, w.shape[-1])
                and _batched_offsets_aligned(offsets, bn_, assume_aligned))
    if _takes_pallas(backend, tileable, "rolling_matmul_batched"):
        return _rolling_mm_batched_pallas(x, w, offsets, win, bm=bm, bn=bn,
                                          bk=bk,
                                          interpret=interpret_mode())
    return jax.vmap(ref.rolling_matmul_ref,
                    in_axes=(0, 0, 0, None))(x, w, offsets, win)


def _rolling_b_dx_arm(dy, w, offsets, win, backend, bm, bn, bk,
                      assume_aligned):
    _, M, _ = dy.shape
    K = w.shape[1]
    bm_, bn_, bk_ = min(bm, M), min(bn, K), min(bk, win)
    tileable = (M % bm_ == 0 and K % bn_ == 0 and win % bk_ == 0
                and _lane_block_ok(bk_, w.shape[-1])
                and _batched_offsets_aligned(offsets, bk_, assume_aligned))
    if _takes_pallas(backend, tileable, "rolling_matmul_batched_dx"):
        return _rolling_dx_batched_pallas(dy, w, offsets, win, bm=bm, bn=bn,
                                          bk=bk,
                                          interpret=interpret_mode())

    def one(dy_b, w_b, off_b):
        wsub = jax.lax.dynamic_slice_in_dim(w_b, off_b, win, axis=1)
        return jax.lax.dot_general(
            dy_b, wsub, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dy_b.dtype)

    return jax.vmap(one)(dy, w, offsets)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _rolling_mm_b(x, w, offsets, win, backend, blocks, assume_aligned):
    return _rolling_b_fwd_arm(x, w, offsets, win, backend, *blocks[0],
                              assume_aligned)


def _rolling_mm_b_fwd(x, w, offsets, win, backend, blocks, assume_aligned):
    y = _rolling_b_fwd_arm(x, w, offsets, win, backend, *blocks[0],
                           assume_aligned)
    return y, (x, w, offsets)


def _rolling_mm_b_bwd(win, backend, blocks, assume_aligned, res, dy):
    """Mirror of the shared-offset VJP, per batch row: dx through the
    batched offset-prefetch backward kernel (vmapped oracle fallback), dW
    as a per-row window scatter-add of ``x[b]^T @ dy[b]``."""
    x, w, offsets = res
    dx = _rolling_b_dx_arm(dy, w, offsets, win, backend, *blocks[1],
                           assume_aligned)

    def dw_one(x_b, dy_b, off_b, w_b):
        dw_win = jax.lax.dot_general(
            x_b, dy_b, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(w_b.dtype)
        return jax.lax.dynamic_update_slice(
            jnp.zeros(w_b.shape, dw_win.dtype), dw_win, (0, off_b))

    dw = jax.vmap(dw_one)(x, dy, offsets, w)
    d_off = np.zeros(np.shape(offsets), jax.dtypes.float0)
    return dx, dw, d_off


_rolling_mm_b.defvjp(_rolling_mm_b_fwd, _rolling_mm_b_bwd)


def rolling_matmul_batched(x, w, offsets, win, backend=None, bm=None,
                           bn=None, bk=None, assume_aligned=False):
    """y[B, M, win] = x[B, M, K] @ w[B, K, offsets[B] : offsets[B]+win],
    differentiable — the batched-offset arm of :func:`rolling_matmul`.

    One window offset per batch row (per-client windows: the staggered
    rolling and random structured schemes).  The Pallas arm prefetches the
    whole offset vector and indexes it with the leading grid coordinate
    (``kernels.rolling_matmul_batched``), so each row reads only its own
    active window of ``w`` from HBM; the jnp arm is the vmapped
    dynamic-slice oracle.  Falls back to the oracle for untileable shapes,
    for concrete offsets off the block grid, and for *traced* offsets
    unless ``assume_aligned=True`` (the scheme's ``grid_multiple``
    certificate).  Custom VJP mirrors :func:`rolling_matmul` per row.
    ``None`` block args resolve through :func:`autotune_blocks`, per
    role."""
    blocks = _role_blocks(x.shape[-2], x.shape[-1], win, x.dtype, backend,
                          bm, bn, bk)
    return _rolling_mm_b(x, w, offsets, win, backend, blocks,
                         assume_aligned)


# -- multi-step form (T windowed matmuls sharing one x and one offset) -------


def _pallas_multi_fwd(x, ws, offset, win, bm, bn, bk):
    """Batchable Pallas multi-step forward: ``ws`` arrives stacked [T, K, N]
    and the whole step group runs as one kernel call.  Under ``jax.vmap``
    (the fused client phase) this lowers to the batched-offset multi kernel
    — or, when weights AND offset are shared across the batch, folds the
    batch into rows exactly like :func:`_pallas_fwd`."""
    interp = interpret_mode()

    @custom_batching.custom_vmap
    def fwd(x, ws, offset):
        return _rolling_mm_multi_pallas(x, ws, offset, win, bm=bm, bn=bn,
                                        bk=bk, interpret=interp)

    @fwd.def_vmap
    def _rule(axis_size, in_batched, x, ws, offset):  # noqa: ANN001
        xb, wb, ob = in_batched
        if not wb and not ob:
            ys = _rolling_mm_multi_pallas(x.reshape(-1, x.shape[-1]), ws,
                                          offset, win,
                                          bm=min(bm, x.shape[-2]), bn=bn,
                                          bk=bk, interpret=interp)
            ys = ys.reshape(ys.shape[0], axis_size, -1, win)
            return jnp.moveaxis(ys, 0, 1), True
        xx = x if xb else jnp.broadcast_to(x[None], (axis_size,) + x.shape)
        ww = (jnp.moveaxis(ws, 0, 1) if wb
              else jnp.broadcast_to(ws[:, None],
                                    (ws.shape[0], axis_size) + ws.shape[1:]))
        oo = jnp.asarray(offset, jnp.int32)
        if not ob:
            oo = jnp.broadcast_to(oo[None], (axis_size,))
        ys = _rolling_mm_batched_multi_pallas(xx, ww, oo, win, bm=bm, bn=bn,
                                              bk=bk, interpret=interp)
        return ys, True

    return fwd(x, ws, jnp.asarray(offset, jnp.int32))


def _multi_fwd_arm(x, ws, offset, win, backend, bm, bn, bk, assume_aligned):
    M, K = x.shape
    uniform = len({w.shape for w in ws}) == 1
    tileable = uniform and _rolling_tileable(M, K, ws[0].shape[-1], win,
                                             offset, bm, bn, bk,
                                             assume_aligned)
    if _takes_pallas(backend, tileable, "rolling_matmul_multi"):
        ys = _pallas_multi_fwd(x, jnp.stack(ws), offset, win, bm, bn, bk)
        return tuple(ys[t] for t in range(len(ws)))
    # jnp arm: a literal loop of the single-weight oracle — bitwise
    # identical to T separate rolling_matmul calls, which is what keeps
    # fused == extract exact on CPU when layers route through the multi op.
    return tuple(ref.rolling_matmul_ref(x, w, offset, win) for w in ws)


def _pallas_multi_dx(dys, ws, offset, win, bm, bn, bk):
    """Batchable multi-step backward arm (mirrors :func:`_pallas_multi_fwd`;
    ``dys`` stacked [T, M, win], returns the step-summed dx [M, K])."""
    interp = interpret_mode()

    @custom_batching.custom_vmap
    def bwd(dys, ws, offset):
        return _rolling_dx_multi_pallas(dys, ws, offset, win, bm=bm, bn=bn,
                                        bk=bk, interpret=interp)

    @bwd.def_vmap
    def _rule(axis_size, in_batched, dys, ws, offset):  # noqa: ANN001
        dyb, wb, ob = in_batched
        if not wb and not ob:
            d = jnp.moveaxis(dys, 0, 1)  # [B, T, M, win] -> [T, B, M, win]
            d = d.reshape(d.shape[0], -1, d.shape[-1])
            dx = _rolling_dx_multi_pallas(d, ws, offset, win,
                                          bm=min(bm, dys.shape[-2]), bn=bn,
                                          bk=bk, interpret=interp)
            return dx.reshape(axis_size, -1, ws.shape[-2]), True
        dd = dys if dyb else jnp.broadcast_to(dys[None],
                                              (axis_size,) + dys.shape)
        ww = (jnp.moveaxis(ws, 0, 1) if wb
              else jnp.broadcast_to(ws[:, None],
                                    (ws.shape[0], axis_size) + ws.shape[1:]))
        oo = jnp.asarray(offset, jnp.int32)
        if not ob:
            oo = jnp.broadcast_to(oo[None], (axis_size,))
        dx = _rolling_dx_batched_multi_pallas(dd, ww, oo, win, bm=bm, bn=bn,
                                              bk=bk, interpret=interp)
        return dx, True

    return bwd(dys, ws, jnp.asarray(offset, jnp.int32))


def _multi_dx_arm(dys, ws, offset, win, backend, bm, bn, bk, assume_aligned):
    M = dys[0].shape[0]
    K = ws[0].shape[0]
    bm_, bn_, bk_ = min(bm, M), min(bn, K), min(bk, win)
    uniform = len({w.shape for w in ws}) == 1
    tileable = (uniform and M % bm_ == 0 and K % bn_ == 0
                and win % bk_ == 0
                and _lane_block_ok(bk_, ws[0].shape[-1])
                and _offset_aligned(offset, bk_, assume_aligned))
    if _takes_pallas(backend, tileable, "rolling_matmul_multi_dx"):
        return _pallas_multi_dx(jnp.stack(dys), jnp.stack(ws), offset, win,
                                bm, bn, bk)

    def one(dy, w):
        wsub = jax.lax.dynamic_slice_in_dim(w, offset, win, axis=1)
        return jax.lax.dot_general(
            dy, wsub, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dy.dtype)

    # Per-step oracle terms summed pairwise in step order: for the gate/up
    # pair (T=2) this is one f32 add, the same single add JAX's cotangent
    # accumulation performs for two separate rolling_matmul calls.
    out = one(dys[0], ws[0])
    for dy, w in zip(dys[1:], ws[1:]):
        out = out + one(dy, w)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _rolling_mm_multi(x, ws, offset, win, backend, blocks, assume_aligned):
    return _multi_fwd_arm(x, ws, offset, win, backend, *blocks[0],
                          assume_aligned)


def _rolling_mm_multi_fwd(x, ws, offset, win, backend, blocks,
                          assume_aligned):
    ys = _multi_fwd_arm(x, ws, offset, win, backend, *blocks[0],
                        assume_aligned)
    return ys, (x, ws, offset)


def _rolling_mm_multi_bwd(win, backend, blocks, assume_aligned, res, dys):
    """dx accumulates across the T steps inside one kernel call (oracle:
    pairwise sum of per-step dots); each dW is the same window scatter-add
    as the single-weight VJP."""
    x, ws, offset = res
    dys = tuple(dys)
    dx = _multi_dx_arm(dys, ws, offset, win, backend, *blocks[1],
                       assume_aligned)
    dws = []
    for w, dy in zip(ws, dys):
        dw_win = jax.lax.dot_general(
            x, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(w.dtype)
        dws.append(jax.lax.dynamic_update_slice(
            jnp.zeros(w.shape, dw_win.dtype), dw_win, (0, offset)))
    d_off = np.zeros(np.shape(offset), jax.dtypes.float0)
    return dx, tuple(dws), d_off


_rolling_mm_multi.defvjp(_rolling_mm_multi_fwd, _rolling_mm_multi_bwd)


def rolling_matmul_multi(x, ws, offset, win, backend=None, bm=None, bn=None,
                         bk=None, assume_aligned=False):
    """ys[t][M, win] = x[M, K] @ ws[t][K, offset : offset+win] for a tuple
    of weights sharing one activation and one window — differentiable.

    The K-step scan-body fusion: the gated MLP's gate/up pair (and any
    other group of windowed matmuls against the same x and offset) runs as
    ONE Pallas call per direction (``kernels.rolling_matmul.
    rolling_matmul_multi`` forward, ``rolling_matmul_bwd.
    rolling_matmul_dx_multi`` backward), whose grid gains a leading step
    dimension so the next step's W column-block DMA overlaps the previous
    step's MXU work and the x block load amortizes over steps.  The jnp arm
    is a literal loop of the single-weight oracle, bitwise identical to T
    separate :func:`rolling_matmul` calls — so routing layers through this
    op cannot move fused-vs-extract numerics on CPU.  Under ``jax.vmap``
    both Pallas halves lower to the batched-offset multi kernels (or fold
    rows when weights and offset are shared).  ``None`` block args resolve
    through :func:`autotune_blocks`, per role; falls back to the oracle
    loop for untileable shapes, non-uniform weight shapes, and
    unaligned/traced offsets without ``assume_aligned``."""
    ws = tuple(ws)
    blocks = _role_blocks(x.shape[-2], x.shape[-1], win, x.dtype, backend,
                          bm, bn, bk)
    return _rolling_mm_multi(x, ws, offset, win, backend, blocks,
                             assume_aligned)
