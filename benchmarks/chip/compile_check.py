"""Compile a cell's device programs for a described TPU v5e, here, without a chip.

    JAX_PLATFORMS=cpu python3 -m benchmarks.chip.compile_check --train-batch 8 12 16
    JAX_PLATFORMS=cpu python3 -m benchmarks.chip.compile_check --decode [--config <name> ...]

Nothing runs: this says whether the TPU compiler accepts the program and
how many bytes ``compiled.memory_analysis()`` gives it on one chip, which
is how the train configuration's batch was chosen. For each configuration
(``configs/<name>.json``; by default those of the cells whose traffic runs
the step): the train step is the program's own
(``distributed.steps.make_train_step``) with its shardings on a one-chip
mesh; the decode step is the serving engine's ``Model.decode`` at each
cache size the serving traffic of the configuration's cells uses.
"""

from __future__ import annotations

import argparse
import os

from benchmarks.chip import harness


def _one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in ("argument_size_in_bytes", "output_size_in_bytes",
                                            "temp_size_in_bytes", "alias_size_in_bytes",
                                            "generated_code_size_in_bytes")}


def train(c: dict, batch: int, seq_len: int) -> dict:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.distributed.steps import batch_shardings, make_train_step, state_struct_for
    from repro.optim import AdamWConfig

    cfg = harness.program_config(c)
    mesh = Mesh(np.array([_one_chip()]).reshape(1, 1), ("data", "model"))
    opt = AdamWConfig(moment_dtype=cfg.opt_moment_dtype)
    step, st_sh, m_sh = make_train_step(cfg, mesh, opt)
    bstruct = {k: jax.ShapeDtypeStruct((batch, seq_len), np.int32) for k in ("tokens", "labels")}
    b_sh = batch_shardings(bstruct, mesh)
    state = jax.tree_util.tree_map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                                   state_struct_for(cfg, opt), st_sh)
    bstruct = jax.tree_util.tree_map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                                     bstruct, b_sh)
    compiled = jax.jit(step, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, m_sh),
                       donate_argnums=0).lower(state, bstruct).compile()
    return _mem(compiled)


def cell_configs(kind: str) -> list[str]:
    """The configurations of the cells whose traffic is of ``kind``."""
    return sorted({w["config"] for w in harness.benchmark()["workloads"]
                   if harness.traffic(w["traffic"])["kind"] == kind})


def cache_sizes(name: str) -> list[int]:
    """Every prompt plus answer length of the serving traffic of the cells on
    configuration ``name``."""
    sizes = set()
    for w in harness.benchmark()["workloads"]:
        tr = harness.traffic(w["traffic"])
        if w["config"] == name and tr["kind"] == "serve":
            sizes |= {int(p) + int(m) for p in tr["prompt_len"] for m in tr["output_len"]}
    return sorted(sizes)


def decode(c: dict, s_max: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.distributed.steps import model_axes_for
    from repro.models import Model

    cfg = harness.program_config(c)
    sh = SingleDeviceSharding(_one_chip())
    model = Model(cfg)

    def put(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    params = jax.tree_util.tree_map(put, model_axes_for(cfg)[1])
    caches = jax.tree_util.tree_map(put, model.cache_struct(1, s_max))
    compiled = jax.jit(model.decode).lower(
        params, caches, put(jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        put(jax.ShapeDtypeStruct((), jnp.int32))).compile()
    return _mem(compiled)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", nargs="+",
                    help="configuration names (default: those of the cells of each step's kind)")
    ap.add_argument("--train-batch", type=int, nargs="*", default=[])
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--decode", action="store_true")
    args = ap.parse_args(argv)
    harness.add_program_to_path()
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    for name in args.config or cell_configs("train"):
        for b in args.train_batch:
            m = train(harness.config(name), b, args.seq_len)
            total = m["argument_size_in_bytes"] + m["output_size_in_bytes"] \
                + m["temp_size_in_bytes"] - m["alias_size_in_bytes"]
            print(f"train batch {b} x {args.seq_len}: {m}; arguments+outputs+temp-alias "
                  f"{total / 1e9:.3f} GB", flush=True)
    if args.decode:
        for name in args.config or cell_configs("serve"):
            for s_max in cache_sizes(name):
                print(f"decode s_max {s_max}: {decode(harness.config(name), s_max)}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
