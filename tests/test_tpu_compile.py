"""Compile-only rehearsals of the main path for a described TPU v5e.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so these tests
run on a CPU host.  They catch what interpret mode cannot: block shapes
that break Mosaic's tiling rules, kernels that need more VMEM than a core
has, and programs that do not fit the chip's HBM.  Nothing runs, so they
say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process may load libtpu, and under xdist every worker imports this
file while only the worker that runs it may load the library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# TinyLlama-1.1B widths: tokens per client step (mb 1 x seq 512), d_model,
# d_ff and its capacity-0.5 window.
M, K, N, WIN = 512, 2048, 5632, 2816

# HBM the v5e compiler allocates against (its own error messages report
# "of 15.75G hbm").
V5E_HBM_BYTES = 15.75 * 2**30


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "no TPU"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, dtype, sh):
    """(fn, abstract args) of one kernel at TinyLlama widths, blocks as the
    dispatch layer's autotuner picks them."""
    from repro.kernels import dispatch
    from repro.kernels.rolling_matmul import (rolling_matmul,
                                              rolling_matmul_multi)
    from repro.kernels.rolling_matmul_batched import rolling_matmul_batched
    from repro.kernels.rolling_matmul_bwd import rolling_matmul_dx

    role = "dx" if name == "rolling_matmul_dx" else "fwd"
    bm, bn, bk = dispatch.autotune_blocks(M, K, WIN, dtype, role=role)
    blocks = dict(bm=bm, bn=bn, bk=bk, interpret=False)
    off = _spec(sh, (), jnp.int32)
    w = _spec(sh, (K, N), dtype)
    if name == "rolling_matmul":
        return (lambda x, w, o: rolling_matmul(x, w, o, WIN, **blocks),
                (_spec(sh, (M, K), dtype), w, off))
    if name == "rolling_matmul_dx":
        return (lambda dy, w, o: rolling_matmul_dx(dy, w, o, WIN, **blocks),
                (_spec(sh, (M, WIN), dtype), w, off))
    if name == "rolling_matmul_multi":
        return (lambda x, ws, o: rolling_matmul_multi(x, ws, o, WIN,
                                                      **blocks),
                (_spec(sh, (M, K), dtype), _spec(sh, (2, K, N), dtype), off))
    assert name == "rolling_matmul_batched"
    return (lambda x, w, o: rolling_matmul_batched(x, w, o, WIN, **blocks),
            (_spec(sh, (2, M, K), dtype), _spec(sh, (2, K, N), dtype),
             _spec(sh, (2,), jnp.int32)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", ["rolling_matmul", "rolling_matmul_dx",
                                  "rolling_matmul_multi",
                                  "rolling_matmul_batched"])
def test_rolling_matmul_compiles_for_v5e(one_chip, name, dtype):
    fn, args = _kernel_case(name, dtype, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The benchmark cells' batched calls: (clients B, rows M, d_model K; q/k/v
# output width N and window, gate/up output width and window).
CELLS = {
    "ds7b-silo": (1, 4096, 4096, (4096, 2048), (11008, 5504)),
    "phi3-partition": (2, 1024, 3072, (3072, 1536), (8192, 4096)),
}


def _batched_case(name, cell, sh):
    """(fn, abstract args, vmem_limit_bytes) of one batched kernel at a
    cell's shapes, with the blocks the tuner gives its role and the limit
    the call passes to Mosaic."""
    from repro.kernels import dispatch
    from repro.kernels import rolling_matmul_batched as rmb
    from repro.kernels.rolling_matmul import vmem_limit_bytes

    B, M, K, qkv, gate_up = CELLS[cell]
    multi = name.endswith("_multi")
    N, win = gate_up if multi else qkv
    role = "dx" if "_dx" in name else "fwd"
    bm, bn, bk = dispatch.autotune_blocks(M, K, win, jnp.float32, role=role)
    kernel = getattr(rmb, name)
    f32 = jnp.float32
    w = _spec(sh, (2, B, K, N) if multi else (B, K, N), f32)
    if role == "fwd":
        a = _spec(sh, (B, M, K), f32)
    else:
        a = _spec(sh, (B, 2, M, win) if multi else (B, M, win), f32)
    return ((lambda a, w, o: kernel(a, w, o, win, bm=bm, bn=bn, bk=bk,
                                    interpret=False)),
            (a, w, _spec(sh, (B,), jnp.int32)),
            vmem_limit_bytes(bm, bn, bk, 4))


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("name", ["rolling_matmul_batched",
                                  "rolling_matmul_batched_dx",
                                  "rolling_matmul_batched_multi",
                                  "rolling_matmul_batched_dx_multi"])
def test_tuned_batched_kernels_compile_for_v5e(one_chip, name, cell):
    """Mosaic takes the tuned blocks within the VMEM limit the call
    passes (it refuses a kernel whose buffers exceed it)."""
    fn, args, limit = _batched_case(name, cell, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # above the 16 MiB a v5e kernel gets unasked: the compile checks the
    # limit the call passes, not the default
    assert limit > 16 * 2**20


def test_masked_sgd_compiles_for_v5e(one_chip):
    from repro.kernels.masked_update import masked_sgd_2d

    # one d_model x d_ff weight in the kernels' rows x 128-lane layout
    a = _spec(one_chip, (K * N // 128, 128), jnp.float32)
    compiled = jax.jit(
        lambda p, m, g: masked_sgd_2d(p, m, g, 0.05, interpret=False)
    ).lower(a, a, a).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sgd_step_updates_leaves_in_place_for_v5e(one_chip):
    """The client update runs on each leaf in its own layout: no reshape,
    pad or copy of the carried tree around it, and no custom call (which
    would force the default layout) -- here on phi-3-mini's stacked
    two-client leaves, the 32,064-wide head off the 128-lane grid."""
    from repro.kernels import dispatch

    tree = {name: _spec(one_chip, shape, jnp.float32)
            for name, shape in (("w_up", (2, 3072, 8192)),
                                ("w_down", (2, 8192, 3072)),
                                ("head", (2, 3072, 32064)))}
    compiled = jax.jit(
        lambda p, g: dispatch.sgd_step(p, g, 0.05, backend="pallas")
    ).lower(tree, tree).compile()
    text = compiled.as_text()
    for op in ("reshape(", "pad(", "copy(", "tpu_custom_call"):
        assert op not in text, op


def test_fused_round_compiles_for_v5e(one_chip, monkeypatch):
    """One fused window round at TinyLlama-1.1B published widths, cut to 2
    layers and 2 clients: compiles, carries each client's cut window
    (the MLP stacks ``[C, L, D, win]``, never ``[C, L, D, d_ff]``) with
    no Pallas call in it (the cut weights take XLA dots; the kernels are
    compiled on their own above), and fits the chip's HBM, parameters in
    and out included."""
    import dataclasses

    from repro import api
    from repro.configs.base import SubmodelConfig, get_config
    from repro.kernels import dispatch
    from repro.models import build_model

    # the dispatch layer asks the CPU backend which platform it is on
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("tinyllama_1_1b"), n_layers=2)
    model = build_model(cfg, moe_path="dropping", remat=True)
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=2, client_lr=0.05)
    fed = api.fed_round(model, scfg)
    assert fed.use_fused
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype), model.abstract_params())
    batch = {"tokens": _spec(one_chip, (2, 2, 1, 512), jnp.int32)}
    compiled = jax.jit(fed.round).lower(
        params, batch, _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (2,), jnp.uint32)).compile()
    text = compiled.as_text()
    assert f"f32[2,2,{K},{WIN}]" in text
    assert f"f32[2,2,{K},{N}]" not in text
    assert "tpu_custom_call" not in text
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert need <= V5E_HBM_BYTES, need / 2**30
