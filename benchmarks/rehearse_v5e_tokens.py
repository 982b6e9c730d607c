#!/usr/bin/env python3
"""Compile a token cell's train step for a DESCRIBED v5e (no chip attached).

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_v5e_tokens.py <cell> [--per-chip-batch N]

``rehearse_v5e.py`` for a cell whose driver is ``train_tokens``: prints the
compiler's per-device memory for the step at the cell's batch and sequence
length, and the parameters by part.  A count from shapes, never a time.
Only one process may load the TPU's library at a time, so nothing here runs
at import.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--per-chip-batch", type=int, default=0)
    ap.add_argument("--dump-hlo", default="")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(HERE, "workloads", f"{args.cell}.json")) as f:
        cell = json.load(f)
    with open(os.path.join(HERE, "configs", f"{cell['config']}.json")) as f:
        conf = json.load(f)
    if args.per_chip_batch:
        conf["per_chip_batch"] = args.per_chip_batch
    from benchmarks.drivers.train_tokens import program_config
    from benchmarks.rehearse_v5e import report
    from byol_tpu.core.config import resolve
    from byol_tpu.core.precision import get_policy
    from byol_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, SEQUENCE_AXIS
    from byol_tpu.training.build import (build_net, build_tx,
                                         init_variables, step_config)
    from byol_tpu.training.state import create_train_state
    from byol_tpu.training.steps import make_train_step
    chips = int(cell["chips"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(chips, 1, 1),
                (DATA_AXIS, SEQUENCE_AXIS, MODEL_AXIS))
    batch, seq_len = conf["per_chip_batch"] * chips, conf["seq_len"]
    cfg = program_config(conf, seed=0, chips=chips)
    rcfg = resolve(cfg, num_train_samples=conf["schedule"]["steps_per_epoch"]
                   * batch, num_test_samples=batch,
                   output_size=conf["num_classes"], input_shape=(seq_len,))
    net = build_net(rcfg)
    tx, schedule = build_tx(rcfg)
    state = jax.eval_shape(
        lambda k: create_train_state(init_variables(net, rcfg, k), tx),
        jax.random.PRNGKey(0))
    sizes = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]:
        names = [getattr(k, "key", str(k)) for k in path]
        part = names[1] if names[0] == "backbone" else names[0]
        sizes[part] = sizes.get(part, 0) + int(np.prod(leaf.shape))
    print("parameters by part (M):",
          {k: round(v / 1e6, 2) for k, v in sizes.items()},
          "total", round(sum(sizes.values()) / 1e6, 1), flush=True)
    rep = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P(DATA_AXIS))
    on = lambda sh: (lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                    sharding=sh))
    state = jax.tree_util.tree_map(on(rep), state)
    view = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32, sharding=data)
    batch_struct = {"view1": view, "view2": view,
                    "label": jax.ShapeDtypeStruct((batch,), jnp.int32,
                                                  sharding=data)}
    step = make_train_step(net, tx, step_config(rcfg),
                           get_policy(cfg.device.half), lr_schedule=schedule,
                           mesh=mesh)
    with mesh:
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            state, batch_struct).compile()
    report(f"{args.cell} train step, per-chip batch "
           f"{conf['per_chip_batch']} x {seq_len}", compiled)
    cost = compiled.cost_analysis()
    print(f"compiler: {cost.get('flops', 0) / 1e12:.2f} TFLOP, "
          f"{cost.get('bytes accessed', 0) / 1e9:.1f} GB accessed a step",
          flush=True)
    if args.dump_hlo:
        with open(args.dump_hlo, "w") as f:
            f.write(compiled.as_text())


if __name__ == "__main__":
    main()
