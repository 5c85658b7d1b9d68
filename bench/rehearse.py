"""Compile each cell's serving programs at size for a described TPU v5e and
print what the compiler says they hold in device memory.  Needs no chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/rehearse.py [workload ...]

For every cell (default: all in BENCHMARK.json) the decode step
(``serve_step``), each chunk-prefill bucket and the slot reset are lowered
with the cell's weights, cache and mesh as shapes on ``v5e:2x2`` devices
(one device for a one-chip cell, a ``(data, model)`` mesh of the
configuration's shape otherwise) and compiled.  Per program it prints
``memory_analysis()`` per device, and per cell the weights plus the largest
program's arguments, outputs and temporaries that are not aliased to its
arguments, against the 16 GiB a chip holds.  Run it again whenever a cell is
added: the slot counts in ``bench/cells/`` were fixed from its output.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

GiB = 2 ** 30


def _described(chips: int, mesh_shape):
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import AxisType, Mesh

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devs = np.asarray(topo.devices[:chips]).reshape(mesh_shape)
    return Mesh(devs, ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))


def rehearse(cell) -> None:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from repro.distributed import sharding as sh
    from repro.serving import engine, kv_cache as kvc
    from repro.serving import sharded as shd

    from bench import system

    cfg = system.model_config(cell)
    lay = system.layout(cell, cfg)
    rules = sh.rules_for_mesh(_described(cell.chips, cell.config["mesh"]))
    shapes = system.weight_shapes(cfg)
    wsh = system.weight_shardings(cfg, rules, shapes)
    params = jax.tree.map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d),
        shapes, wsh)
    cshapes = jax.eval_shape(lambda: kvc.init_cache_arrays(cfg, lay))
    csh = shd.cache_shardings(cshapes, cfg, lay, rules)
    cache = jax.tree.map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d),
        cshapes, csh)
    rep = jax.sharding.NamedSharding(rules.mesh, jax.sharding.PartitionSpec())
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)

    wbytes = sum(int(x.size) * x.dtype.itemsize
                 for x in jax.tree.leaves(shapes))
    held = sum(int(np.prod(d.shard_shape(s.shape))) * s.dtype.itemsize
               for s, d in zip(jax.tree.leaves(shapes), jax.tree.leaves(wsh)))
    print(f"[rehearse] {cell.name}: {cfg.name}, {lay.batch} slots x "
          f"{lay.max_seq} {lay.kv_format} {lay.layout}; weights "
          f"{wbytes / GiB:.2f} GiB, {held / GiB:.2f} GiB per device",
          flush=True)
    progs = {"serve_step": (jax.jit(engine.make_serve_step(cfg, lay, rules)),
                            (params, cache, i32((lay.batch, 1))))}
    budget = int(cell.serving["chunk_budget"])
    chunk = jax.jit(engine.make_prefill_chunk(cfg, lay, rules),
                    donate_argnums=(1,))
    for C in engine.default_buckets(budget):
        progs[f"prefill_chunk[{C}]"] = (
            chunk, (params, cache, i32((1, C)), i32(()), i32(()), i32(())))
    progs["reset_slot"] = (
        jax.jit(lambda c, s: kvc.reset_slot(c, lay, s), donate_argnums=(0,)),
        (cache, i32(())))
    worst = 0
    for name, (fn, args) in progs.items():
        try:
            m = fn.lower(*args).compile().memory_analysis()
        except jax.errors.JaxRuntimeError as e:  # the compiler's own refusal
            print(f"[rehearse] {cell.name} {name}: refused: {e}", flush=True)
            return
        arg, out = m.argument_size_in_bytes, m.output_size_in_bytes
        tmp, alias = m.temp_size_in_bytes, m.alias_size_in_bytes
        live = arg + out - alias + tmp
        worst = max(worst, live)
        print(f"[rehearse] {cell.name} {name}: per device arguments "
              f"{arg / GiB:.2f} GiB, outputs {out / GiB:.2f}, aliased "
              f"{alias / GiB:.2f}, temporaries {tmp / GiB:.2f} -> "
              f"{live / GiB:.2f} GiB live", flush=True)
    print(f"[rehearse] {cell.name}: largest program holds {worst / GiB:.2f} "
          f"GiB per device of 16.00 (weights and cache included)", flush=True)


def main(argv) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    import jax

    from bench.spec import load_cell, workload_names

    # compile as the chip would run it: the program resolves its decode
    # attend and kernel dispatch from the default backend
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    names = argv or workload_names(root)
    for name in names:
        rehearse(load_cell(name, root))


if __name__ == "__main__":
    main(sys.argv[1:])
