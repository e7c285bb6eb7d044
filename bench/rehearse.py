"""Compile a cell's round for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python -m bench.rehearse --workload ds7b-silo

Builds the cell's model and round as the benchmark does, compiles the round
for one chip of a described ``v5e:2x2`` (or for its four chips, on a mesh,
where the cell asks for four), and prints the compiler's
``memory_analysis()`` and the Pallas kernels in the program.  Nothing
runs: it shows what the chip's compiler refuses (tiling, VMEM, a program
that does not fit) before chip time is spent.  Run it on a CPU host.
"""
from __future__ import annotations

import argparse
import os

GIB = 2**30


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m bench.rehearse",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from bench import spec
    from repro import api
    from repro.kernels import dispatch
    from repro.models import build_model

    cell = spec.load_cell(args.workload)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # The dispatch layer asks the (CPU) backend which platform it is on.
    dispatch.on_tpu = lambda: True
    cfg = cell.model_config()
    model = build_model(cfg, remat=True)
    mesh = None
    if cell.mix.get("mesh_agg"):
        mesh = Mesh(np.array(topo.devices[:cell.chips]).reshape(cell.chips, 1),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        sh = NamedSharding(mesh, PartitionSpec())
    else:
        sh = SingleDeviceSharding(topo.devices[0])
    fed = api.fed_round(model, cell.submodel_config(), mesh=mesh,
                        mesh_agg=cell.mix.get("mesh_agg") or "gather")
    m = cell.mix
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        model.abstract_params())
    tokens = jax.ShapeDtypeStruct(
        (m["local_steps"], m["clients"], m["seqs_per_step"], m["seq_len"]),
        jnp.int32, sharding=sh)
    compiled = jax.jit(fed.round).lower(
        params, {"tokens": tokens}, jax.ShapeDtypeStruct((), jnp.int32,
                                                         sharding=sh),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sh)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{cell.name}: {cfg.n_layers} layers, {m['clients']} clients on "
          f"{cell.chips} chip(s); fused client phase {fed.use_fused}")
    print(f"memory per chip (GiB): arguments "
          f"{mem.argument_size_in_bytes / GIB:.2f}, outputs "
          f"{mem.output_size_in_bytes / GIB:.2f}, temp "
          f"{mem.temp_size_in_bytes / GIB:.2f}, alias "
          f"{mem.alias_size_in_bytes / GIB:.2f}; arguments + outputs + "
          f"temp - alias {total / GIB:.3f}")
    print(f"tpu_custom_call: {compiled.as_text().count('tpu_custom_call')}; "
          f"oracle fallbacks: {dict(dispatch.ORACLE_FALLBACKS)}")


if __name__ == "__main__":
    main()
