"""Compile each cell's timed program at its real size for a described TPU
v5e, without the chip, and print what the compiler says of it.

    JAX_PLATFORMS=cpu REPRO_PALLAS=on python bench/rehearse.py [cell ...]

For every named cell (all by default) it lowers the program the window
drives (the serve quantum of every C the managed schedule can choose, the
train step, the managed Jacobi solve on one chip or on the v5e:2x2 mesh),
compiles it for the described chip, prints ``memory_analysis()`` and
whether a Pallas kernel (``tpu_custom_call``) is in it.  For the train
cell it also prints the deepest cut of the configuration whose compiled
step fits 95% of one chip's memory, which its configuration file records.
Nothing runs, so nothing here is a time.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402

#: one v5e chip's bytes_limit as the runtime reports it on the chip
V5E_BYTES_LIMIT = 16909336064
#: share of it a train step may claim (the rest is runtime slack)
TRAIN_SHARE = 0.95


def report(what: str, compiled) -> int:
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    kernel = "tpu_custom_call" in compiled.as_text()
    print(f"{what}: args {ma.argument_size_in_bytes} out "
          f"{ma.output_size_in_bytes} alias {ma.alias_size_in_bytes} temp "
          f"{ma.temp_size_in_bytes} -> {need} B "
          f"({need / V5E_BYTES_LIMIT:.1%} of a chip); Pallas kernel: "
          f"{kernel}", flush=True)
    return need


def sds_tree(specs, mesh, dtype):
    import jax
    from jax.sharding import NamedSharding
    from repro.parallel.sharding import ParamSpec
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype,
                                       sharding=NamedSharding(mesh,
                                                              s.pspec())),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def serve(cell, devices) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.models.model import Model
    from repro.parallel.sharding import MeshCtx
    from repro.serve.engine import build_paged_step
    from bench.drivers import serve as drv
    from bench.model import model_config

    c = cell.config
    mc = model_config(c)
    mesh = Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))
    model = Model(mc, MeshCtx.from_mesh(mesh))
    s = c["serve"]
    pages = s["slots"] * (s["max_seq"] // s["page_size"])
    cache_sds, cache_ps = model.paged_cache_specs(s["slots"], pages,
                                                  s["page_size"])
    params = sds_tree(model.param_specs(), mesh, jnp.dtype(mc.dtype))
    cache = jax.tree.map(lambda a, p: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, p)), cache_sds,
        cache_ps)
    rep = NamedSharding(mesh, P())
    vec = jax.ShapeDtypeStruct((s["slots"],), jnp.int32, sharding=rep)
    table = jax.ShapeDtypeStruct((s["slots"], s["max_seq"] // s["page_size"]),
                                 jnp.int32, sharding=rep)
    for chunk in drv.schedule_chunks():
        toks = jax.ShapeDtypeStruct((s["slots"], chunk), jnp.int32,
                                    sharding=rep)
        fn = build_paged_step(model, mesh, cache_ps, chunk)
        report(f"{cell.name} quantum C={chunk}",
               fn.lower(params, cache, table, toks, vec, vec, vec).compile())


def train(cell, devices) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.models.model import Model
    from repro.optim.adamw import AdamWConfig
    from repro.parallel.sharding import MeshCtx
    from repro.train.train_loop import build_train_step
    from bench.model import model_config

    c, job = cell.config, cell.traffic
    mesh = Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))
    best = None
    for depth in range(c["num_hidden_layers"] + 1, 0, -1):
        mc = dataclasses.replace(model_config(c), n_layers=depth)
        model = Model(mc, MeshCtx.from_mesh(mesh))
        o = c["optimizer"]
        opt_cfg = AdamWConfig(lr=o["lr"], moment_dtype=o["moment_dtype"])
        step, pshard, bshard = build_train_step(model, opt_cfg, mesh)
        params = sds_tree(model.param_specs(), mesh, jnp.dtype(mc.dtype))
        mom = sds_tree(model.param_specs(), mesh, jnp.dtype(o["moment_dtype"]))
        opt = {"mu": mom, "nu": mom, "step": jax.ShapeDtypeStruct(
            (), jnp.int32, sharding=NamedSharding(mesh, P()))}
        batch = {k: jax.ShapeDtypeStruct((job["batch"], job["seq"]),
                                         jnp.int32, sharding=bshard[k])
                 for k in ("tokens", "labels")}
        try:
            compiled = step.lower(params, opt, batch).compile()
        except jax.errors.JaxRuntimeError as e:
            print(f"{cell.name} step, {depth} layers: does not fit "
                  f"({str(e).splitlines()[0][:120]})", flush=True)
            continue
        need = report(f"{cell.name} step, {depth} layers", compiled)
        if need <= TRAIN_SHARE * V5E_BYTES_LIMIT:
            best = depth
            break
    print(f"{cell.name}: deepest cut that fits {TRAIN_SHARE:.0%} of a chip:"
          f" {best} layers (configuration file: "
          f"{c['num_hidden_layers']})", flush=True)


def halo(cell, devices) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import halo as halo_mod, managed
    from repro.parallel.sharding import smap

    c = cell.config
    n = cell.chips
    mesh = Mesh(np.array(devices[:n]), ("x",))
    rows, cols = c["rows_per_chip"] * n, c["cols"]
    k = managed.resolve_halo_aggregation("x", n, rows // n, cols).k
    sh = NamedSharding(mesh, P("x", None))
    grid = jax.ShapeDtypeStruct((rows, cols), jnp.float32, sharding=sh)
    fn = jax.jit(smap(lambda a, b: halo_mod.jacobi_solve(
        a, b, "x", c["sweeps_per_check"], "aggregated", k=k,
        engine="pallas"), mesh, in_specs=(P("x", None),) * 2,
        out_specs=P("x", None)))
    report(f"{cell.name} solve (k={k}, {n} chips)",
           fn.lower(grid, grid).compile())


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cells", nargs="*")
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.cells or [w["name"] for w in harness.benchmark()["workloads"]]
    for name in names:
        cell = harness.load_cell(name)
        kind = cell.traffic["kind"]
        {"serve": serve, "train": train, "halo": halo}[kind](cell,
                                                             topo.devices)


if __name__ == "__main__":
    main()
