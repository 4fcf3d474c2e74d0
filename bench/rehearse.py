"""Compile each cell's training step for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [cell ...]
    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config qwen2.5-14b-1L \\
        --traffic muon-4x2048 --chips 4      # a candidate cell

For every cell of ``BENCHMARK.json`` (or those named), the program's step is
built as ``ResilientLoop`` builds it (``make_train_step`` on the dedication
plan, the state donated), lowered at the cell's batch with every argument
placed on a described ``v5e:2x2`` (one chip, or a (1, 4) mesh over all four
as ``remesh()`` lays it), and compiled by the TPU compiler.  It prints the
compiled ``memory_analysis()`` per device and the count of each collective
in the compiled HLO.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")


def rehearse(config: dict, traffic: dict, chips: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from bench.drivers.train import program_config
    from repro.core import api
    from repro.core.muon import MuonConfig
    from repro.models import model_fns
    from repro.runtime.elastic import make_mesh, viable_mesh_shape
    from repro.train.step import init_state, make_train_step

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    arch = program_config(config, None)
    B, S = int(traffic["batch"]), int(traffic["seq_len"])
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(lambda k: model_fns(arch).init(arch, k), key)
    if chips == 1:
        mesh = None
        plan = api.dedicate_params(shapes, num_owners=1, strategy="greedy")
    else:
        mesh = make_mesh(viable_mesh_shape(chips), ("data", "model"),
                         topo.devices[:chips])
        plan = api.dedicate_params(shapes, mesh=mesh, strategy="greedy")
    opt = api.Muon(plan, mesh, config=MuonConfig(**traffic["optimizer"]))
    step = make_train_step(arch, opt, mesh, donate=True)
    state = jax.eval_shape(lambda: init_state(arch, opt, key, mesh=mesh))
    one = SingleDeviceSharding(topo.devices[0])
    if mesh is None:
        shardings = jax.tree.map(lambda _: one, state)
    else:   # the shardings init_state gives the state on the mesh
        shardings = jax.jit(lambda: init_state(arch, opt, key, mesh=mesh)) \
            .lower().compile().output_shardings
    state = jax.tree.map(lambda x, sh: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sh), state, shardings)
    bsh = one if mesh is None else NamedSharding(mesh, P())
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bsh)
    t0 = time.perf_counter()
    compiled = step.lower(state, {"tokens": tok, "labels": tok}).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    gib = 2.0 ** 30
    return {
        "compile_s": round(time.perf_counter() - t0, 1),
        "arguments_gib": mem.argument_size_in_bytes / gib,
        "aliased_gib": mem.alias_size_in_bytes / gib,
        "temp_gib": mem.temp_size_in_bytes / gib,
        "live_gib": (mem.argument_size_in_bytes
                     + mem.temp_size_in_bytes) / gib,
        "collectives": {c: len(re.findall(rf"\s{c}(?:-start)?\(", text))
                        for c in COLLECTIVES},
        "tpu_custom_calls": text.count("tpu_custom_call"),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    if args.config:
        todo = [{"name": f"{args.config}.{args.traffic}",
                 "config": args.config, "traffic": args.traffic,
                 "chips": args.chips}]
    else:
        todo = [w for w in spec["workloads"]
                if not args.cells or w["name"] in args.cells]
    for w in todo:
        config = json.loads((ROOT / "bench" / "configs"
                             / f"{w['config']}.json").read_text())
        traffic = json.loads((ROOT / "bench" / "traffic"
                              / f"{w['traffic']}.json").read_text())
        out = rehearse(config, traffic, int(w["chips"]))
        print(json.dumps({"cell": w["name"], "chips": w["chips"], **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
