#!/usr/bin/env python3
"""Compile a cell's training step at its full size for a described TPU
v5e, without the chip, and print what the compiler says of it.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_check.py \
        --workload NAME [--topology v5e:2x2]

The TPU compiler that comes with JAX compiles for a chip that is described
and not attached; it refuses a step that does not fit the chip's memory
or a kernel it cannot lower. The step is built as the benchmark builds it
(``systems.build``), from shapes only. Prints the bytes of the arguments
and temporaries per device, the Mosaic kernel calls and the collectives.
Nothing runs, so no time comes from here.
"""
from __future__ import annotations

import argparse
import collections
import os
import pathlib
import re
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--hlo", type=pathlib.Path, default=None,
                    help="also write the compiled step's HLO text here")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(run.HERE), str(run.ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from chipbench import spec, traffic
    from repro.kernels import ops
    from reference.weights import seed_key

    # the CPU backend would otherwise pick interpret mode: no kernel
    ops._interpret = lambda override: False if override is None else override
    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    o = cell.traffic["optimizer"]
    mesh = None
    if o["comm"] == "axis":
        mesh = jax.sharding.Mesh(topo.devices[:o["workers"]], ("worker",))
    from chipbench import systems
    system = systems.build(cell.config, cell.traffic, mesh=mesh)
    tr = system.trainer
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        def leaf(x):
            if mesh is None:
                s = one
            else:
                s = NamedSharding(mesh, PartitionSpec("worker")
                                  if x.ndim and x.shape[0] == o["workers"]
                                  else PartitionSpec())
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
        return jax.tree_util.tree_map(leaf, tree)

    with system.context():
        params = jax.eval_shape(system.make_params, seed_key(0))
        state = jax.eval_shape(tr.init, params)
        batch = jax.eval_shape(
            lambda k: traffic.make_pool(k, cell.config, cell.traffic)[0],
            seed_key(0))
        compiled = tr._step.lower(place(state), place(batch)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    if args.hlo is not None:
        args.hlo.write_text(text)
    ops_seen = collections.Counter(
        m.group(1) for m in re.finditer(
            r"= [^=]*? (collective-permute|all-reduce|all-gather|all-to-all|"
            r"reduce-scatter)(?:-start)?\(", text))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"[compile] {cell.name} for {args.topology}: {n_params} params "
          f"per worker; arguments {mem.argument_size_in_bytes / 1e9:.3f} GB"
          f", outputs {mem.output_size_in_bytes / 1e9:.3f} GB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB per device; "
          f"tpu_custom_call x{text.count('tpu_custom_call')}; "
          f"collectives {dict(ops_seen)}")
    flops = compiled.cost_analysis()
    flops = flops[0] if isinstance(flops, list) else flops
    print(f"[compile] cost_analysis flops {flops.get('flops', 0):.4e}, "
          f"bytes accessed {flops.get('bytes accessed', 0):.4e}; model "
          f"FLOPs per step {system.flops_per_step:.4e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
