"""Interpret-mode matrix for the rolling-window Pallas kernels.

Every Pallas arm of the fused window round — shared-offset forward/backward,
their single-call multi-step (K-step) forms, the batched per-client-offset
forms, and the intra-chunk SSD kernel — runs here under ``interpret=True``
on CPU against the pure-jnp oracles, over aligned, unaligned-tail (dims not
multiples of 128, covered by smaller divisor blocks — the shapes the
dispatch autotuner picks blocks for), and batched-offset shapes.  TPU runs
compile the identical kernel bodies, so this matrix is the CI pin on the
kernel logic itself: index maps, scalar-prefetch offset arithmetic, and
cross-step accumulator reuse.

Dedicated CI job: ``kernels-interpret`` (see .github/workflows/ci.yml).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.rolling_matmul import rolling_matmul, rolling_matmul_multi
from repro.kernels.rolling_matmul_batched import (
    rolling_matmul_batched, rolling_matmul_batched_dx,
    rolling_matmul_batched_dx_multi, rolling_matmul_batched_multi)
from repro.kernels.rolling_matmul_bwd import (rolling_matmul_dx,
                                              rolling_matmul_dx_multi)
from repro.kernels.ssd_chunk import ssd_chunk_intra

# (M, K, N, offset, win, (bm, bn, bk)) — aligned 128-tile shapes plus
# unaligned-tail shapes whose dims only divide by smaller blocks.
SHAPES = [
    pytest.param(128, 256, 512, 0, 256, (128, 128, 128), id="aligned"),
    pytest.param(128, 256, 512, 256, 256, (128, 128, 128),
                 id="aligned-end"),
    pytest.param(192, 320, 576, 64, 192, (64, 64, 64),
                 id="unaligned-tail"),
    pytest.param(64, 96, 160, 32, 64, (32, 32, 32), id="small-blocks"),
]


def _xw(M, K, N, dtype=jnp.float32, lead=()):
    x = jax.random.normal(jax.random.PRNGKey(0), lead + (M, K), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), lead + (K, N), dtype)
    return x, w


def _assert_close(got, want, dtype=jnp.float32):
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


# -- shared-offset forward / backward ---------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,K,N,off,win,blocks", SHAPES)
def test_rolling_matmul_interpret(M, K, N, off, win, blocks, dtype):
    bm, bn, bk = blocks
    x, w = _xw(M, K, N, dtype)
    y = rolling_matmul(x, w, off, win, bm=bm, bn=bn, bk=bk, interpret=True)
    assert y.shape == (M, win) and y.dtype == dtype
    _assert_close(y, ref.rolling_matmul_ref(x, w, off, win), dtype)


@pytest.mark.parametrize("M,K,N,off,win,blocks", SHAPES)
def test_rolling_matmul_dx_interpret(M, K, N, off, win, blocks):
    bm, bn, bk = blocks
    _, w = _xw(M, K, N)
    dy = jax.random.normal(jax.random.PRNGKey(2), (M, win))
    dx = rolling_matmul_dx(dy, w, off, win, bm=bm, bn=bn, bk=bk,
                           interpret=True)
    assert dx.shape == (M, K)
    wsub = jax.lax.dynamic_slice_in_dim(w, off, win, axis=1)
    _assert_close(dx, dy @ wsub.T)


# -- multi-step (single-call K-step) arms -----------------------------------


@pytest.mark.parametrize("T", [1, 2, 3])
@pytest.mark.parametrize("M,K,N,off,win,blocks", SHAPES)
def test_rolling_matmul_multi_interpret(M, K, N, off, win, blocks, T):
    bm, bn, bk = blocks
    x, _ = _xw(M, K, N)
    ws = jax.random.normal(jax.random.PRNGKey(3), (T, K, N))
    ys = rolling_matmul_multi(x, ws, off, win, bm=bm, bn=bn, bk=bk,
                              interpret=True)
    assert ys.shape == (T, M, win)
    want = jnp.stack([ref.rolling_matmul_ref(x, ws[t], off, win)
                      for t in range(T)])
    _assert_close(ys, want)


@pytest.mark.parametrize("T", [1, 2, 3])
@pytest.mark.parametrize("M,K,N,off,win,blocks", SHAPES)
def test_rolling_matmul_dx_multi_interpret(M, K, N, off, win, blocks, T):
    bm, bn, bk = blocks
    ws = jax.random.normal(jax.random.PRNGKey(3), (T, K, N))
    dys = jax.random.normal(jax.random.PRNGKey(4), (T, M, win))
    dx = rolling_matmul_dx_multi(dys, ws, off, win, bm=bm, bn=bn, bk=bk,
                                 interpret=True)
    assert dx.shape == (M, K)
    want = sum(dys[t] @ jax.lax.dynamic_slice_in_dim(
        ws[t], off, win, axis=1).T for t in range(T))
    _assert_close(dx, want)


# -- batched per-client offsets ---------------------------------------------

# per-client offsets exercise off[b] indexing incl. the 0 and max-shift rows
def _offsets(B, N, win, bn):
    hi = (N - win) // bn
    return jnp.asarray([(b * max(hi, 1) // max(B - 1, 1)) % (hi + 1)
                        for b in range(B)], jnp.int32) * bn


@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("M,K,N,off,win,blocks", SHAPES)
def test_rolling_matmul_batched_interpret(M, K, N, off, win, blocks, B):
    bm, bn, bk = blocks
    x, w = _xw(M, K, N, lead=(B,))
    offs = _offsets(B, N, win, bn)
    y = rolling_matmul_batched(x, w, offs, win, bm=bm, bn=bn, bk=bk,
                               interpret=True)
    assert y.shape == (B, M, win)
    want = jnp.stack([ref.rolling_matmul_ref(x[b], w[b], offs[b], win)
                      for b in range(B)])
    _assert_close(y, want)


@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("M,K,N,off,win,blocks", SHAPES)
def test_rolling_matmul_batched_dx_interpret(M, K, N, off, win, blocks, B):
    bm, bn, bk = blocks
    _, w = _xw(M, K, N, lead=(B,))
    dy = jax.random.normal(jax.random.PRNGKey(2), (B, M, win))
    offs = _offsets(B, N, win, bk)
    dx = rolling_matmul_batched_dx(dy, w, offs, win, bm=bm, bn=bn, bk=bk,
                                   interpret=True)
    assert dx.shape == (B, M, K)
    want = jnp.stack([dy[b] @ jax.lax.dynamic_slice_in_dim(
        w[b], offs[b], win, axis=1).T for b in range(B)])
    _assert_close(dx, want)


@pytest.mark.parametrize("B,T", [(2, 2), (4, 3)])
@pytest.mark.parametrize("M,K,N,off,win,blocks", SHAPES)
def test_rolling_matmul_batched_multi_interpret(M, K, N, off, win, blocks,
                                                B, T):
    bm, bn, bk = blocks
    x, _ = _xw(M, K, N, lead=(B,))
    ws = jax.random.normal(jax.random.PRNGKey(3), (T, B, K, N))
    offs = _offsets(B, N, win, bn)
    ys = rolling_matmul_batched_multi(x, ws, offs, win, bm=bm, bn=bn, bk=bk,
                                      interpret=True)
    assert ys.shape == (B, T, M, win)
    want = jnp.stack([
        jnp.stack([ref.rolling_matmul_ref(x[b], ws[t, b], offs[b], win)
                   for t in range(T)]) for b in range(B)])
    _assert_close(ys, want)


@pytest.mark.parametrize("B,T", [(2, 2), (4, 3)])
@pytest.mark.parametrize("M,K,N,off,win,blocks", SHAPES)
def test_rolling_matmul_batched_dx_multi_interpret(M, K, N, off, win,
                                                   blocks, B, T):
    bm, bn, bk = blocks
    ws = jax.random.normal(jax.random.PRNGKey(3), (T, B, K, N))
    dys = jax.random.normal(jax.random.PRNGKey(4), (B, T, M, win))
    offs = _offsets(B, N, win, bk)
    dx = rolling_matmul_batched_dx_multi(dys, ws, offs, win, bm=bm, bn=bn,
                                         bk=bk, interpret=True)
    assert dx.shape == (B, M, K)
    want = jnp.stack([
        sum(dys[b, t] @ jax.lax.dynamic_slice_in_dim(
            ws[t, b], offs[b], win, axis=1).T for t in range(T))
        for b in range(B)])
    _assert_close(dx, want)


# -- rectangular blocks, as the VMEM-sized tuner picks them ----------------

# (M, K, N, win, forward (bm, bn, bk), dx (bm, bn, bk)): the window edge
# (forward bn, dx bk) stays at 128 while the offset-free edges grow — bm
# to 256 or more, the forward's bk to 512 or all of K, dx's bn over K.
RECT_SHAPES = [
    pytest.param(256, 512, 768, 256, (256, 128, 512), (256, 512, 128),
                 id="bk=K"),
    pytest.param(512, 1024, 1280, 384, (256, 128, 512), (512, 256, 128),
                 id="bm256-bk512"),
]


@pytest.mark.parametrize("M,K,N,win,fwd,dxb", RECT_SHAPES)
def test_batched_fwd_rect_blocks_interpret(M, K, N, win, fwd, dxb):
    B = 3
    bm, bn, bk = fwd
    x, w = _xw(M, K, N, lead=(B,))
    offs = _offsets(B, N, win, 128)
    y = rolling_matmul_batched(x, w, offs, win, bm=bm, bn=bn, bk=bk,
                               interpret=True)
    want = jnp.stack([ref.rolling_matmul_ref(x[b], w[b], offs[b], win)
                      for b in range(B)])
    _assert_close(y, want)


@pytest.mark.parametrize("M,K,N,win,fwd,dxb", RECT_SHAPES)
def test_batched_dx_rect_blocks_interpret(M, K, N, win, fwd, dxb):
    B = 3
    bm, bn, bk = dxb
    _, w = _xw(M, K, N, lead=(B,))
    dy = jax.random.normal(jax.random.PRNGKey(2), (B, M, win))
    offs = _offsets(B, N, win, 128)
    dx = rolling_matmul_batched_dx(dy, w, offs, win, bm=bm, bn=bn, bk=bk,
                                   interpret=True)
    want = jnp.stack([dy[b] @ jax.lax.dynamic_slice_in_dim(
        w[b], offs[b], win, axis=1).T for b in range(B)])
    _assert_close(dx, want)


@pytest.mark.parametrize("M,K,N,win,fwd,dxb", RECT_SHAPES)
def test_batched_multi_rect_blocks_interpret(M, K, N, win, fwd, dxb):
    B, T = 3, 2
    bm, bn, bk = fwd
    x, _ = _xw(M, K, N, lead=(B,))
    ws = jax.random.normal(jax.random.PRNGKey(3), (T, B, K, N))
    offs = _offsets(B, N, win, 128)
    ys = rolling_matmul_batched_multi(x, ws, offs, win, bm=bm, bn=bn, bk=bk,
                                      interpret=True)
    want = jnp.stack([
        jnp.stack([ref.rolling_matmul_ref(x[b], ws[t, b], offs[b], win)
                   for t in range(T)]) for b in range(B)])
    _assert_close(ys, want)


@pytest.mark.parametrize("M,K,N,win,fwd,dxb", RECT_SHAPES)
def test_batched_dx_multi_rect_blocks_interpret(M, K, N, win, fwd, dxb):
    B, T = 3, 2
    bm, bn, bk = dxb
    ws = jax.random.normal(jax.random.PRNGKey(3), (T, B, K, N))
    dys = jax.random.normal(jax.random.PRNGKey(4), (B, T, M, win))
    offs = _offsets(B, N, win, 128)
    dx = rolling_matmul_batched_dx_multi(dys, ws, offs, win, bm=bm, bn=bn,
                                         bk=bk, interpret=True)
    want = jnp.stack([
        sum(dys[b, t] @ jax.lax.dynamic_slice_in_dim(
            ws[t, b], offs[b], win, axis=1).T for t in range(T))
        for b in range(B)])
    _assert_close(dx, want)


def test_dispatch_dx_keeps_its_window_block(monkeypatch):
    """Role coupling: the tuner grows the forward's bk to all of K (512),
    past the window; dx must still count the traced, certified offsets in
    128-blocks.  Were dx to inherit the forward's triple, its window block
    would be min(512, win) = 256 and the offset 128 would floor to 0."""
    from repro.kernels import dispatch

    dispatch.clear_block_cache()
    monkeypatch.setattr(dispatch, "_BLOCK_OVERRIDE", None)
    B, M, K, N, win = 2, 256, 512, 640, 256
    assert dispatch.autotune_blocks(M, K, win, role="fwd")[2] == K
    assert dispatch.autotune_blocks(M, K, win, role="dx")[2] == 128
    x, w = _xw(M, K, N, lead=(B,))
    offs = jnp.asarray([128, 384], jnp.int32)
    ct = jax.random.normal(jax.random.PRNGKey(5), (B, M, win))

    def loss(x, offs, backend, aligned):
        y = dispatch.rolling_matmul_batched(x, w, offs, win, backend=backend,
                                            assume_aligned=aligned)
        return (y * ct).sum()

    before = dict(dispatch.ORACLE_FALLBACKS)
    got = jax.jit(jax.grad(lambda x, o: loss(x, o, "pallas", True)))(x, offs)
    assert dict(dispatch.ORACLE_FALLBACKS) == before
    want = jax.grad(lambda x, o: loss(x, o, "jnp", False))(x, offs)
    _assert_close(got, want)
    dispatch.clear_block_cache()


# -- intra-chunk SSD kernel -------------------------------------------------


@pytest.mark.parametrize("nh,hd,N,Q,nh_block", [
    (4, 8, 16, 16, 0), (8, 16, 32, 32, 4), (6, 8, 16, 16, 2),
])
def test_ssd_chunk_interpret_vs_recurrent_oracle(nh, hd, N, Q, nh_block):
    Bt, nc = 2, 3
    x = jax.random.normal(jax.random.PRNGKey(0), (Bt, nc, Q, nh, hd)) * 0.5
    dt = jax.nn.softplus(
        jax.random.normal(jax.random.PRNGKey(1), (Bt, nc, Q, nh)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (nh,)) * 0.3)
    B = jax.random.normal(jax.random.PRNGKey(3), (Bt, nc, Q, N)) * 0.5
    C = jax.random.normal(jax.random.PRNGKey(4), (Bt, nc, Q, N)) * 0.5
    y, h = ssd_chunk_intra(x, dt, A, B, C, nh_block=nh_block, interpret=True)
    assert y.shape == (Bt, nc, Q, nh, hd) and h.shape == (Bt, nc, nh, hd, N)
    for b in range(Bt):
        for c in range(nc):
            yw, hw = ref.ssd_chunk_ref(x[b, c], dt[b, c], A, B[b, c],
                                       C[b, c])
            _assert_close(y[b, c], yw)
            _assert_close(h[b, c], hw)


@pytest.mark.parametrize("off,win,nh_block", [(2, 4, 2), (0, 4, 2),
                                              (4, 4, 0)])
def test_ssd_chunk_head_window_interpret(off, win, nh_block):
    """The head-window arm (scalar-prefetch offset on the head grid) ==
    the recurrent oracle on host-sliced heads."""
    Bt, nc, Q, nh, hd, N = 1, 2, 16, 8, 8, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (Bt, nc, Q, nh, hd)) * 0.5
    dt = jax.nn.softplus(
        jax.random.normal(jax.random.PRNGKey(1), (Bt, nc, Q, nh)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (nh,)) * 0.3)
    B = jax.random.normal(jax.random.PRNGKey(3), (Bt, nc, Q, N)) * 0.5
    C = jax.random.normal(jax.random.PRNGKey(4), (Bt, nc, Q, N)) * 0.5
    y, h = ssd_chunk_intra(x, dt, A, B, C, nh_block=nh_block,
                           head_offset=off, head_win=win, interpret=True)
    assert y.shape == (Bt, nc, Q, win, hd)
    for b in range(Bt):
        for c in range(nc):
            yw, hw = ref.ssd_chunk_ref(x[b, c, :, off:off + win],
                                       dt[b, c, :, off:off + win],
                                       A[off:off + win], B[b, c], C[b, c])
            _assert_close(y[b, c], yw)
            _assert_close(h[b, c], hw)
