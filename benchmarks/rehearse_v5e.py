#!/usr/bin/env python3
"""Compile a cell's programs for a DESCRIBED v5e (no chip attached).

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_v5e.py <cell> [--reference]

Prints the compiler's per-device memory for the cell's train step (on one
described chip, or on the ``data=N`` mesh of the cell's chips) and, with
``--reference``, for the widest segment of the float32 reference's
layer-by-layer backward at the cell's batch.  A count from shapes, never a
time; what the chip's compiler refuses here costs no chip time.  Run by
hand before a cell's first chip call; only one process may load the TPU's
library at a time, so nothing here runs at import.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def gib(n):
    return f"{n / 2 ** 30:.2f} GiB"


def report(tag, compiled):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"{tag}: per device {gib(total)} (args "
          f"{gib(ma.argument_size_in_bytes)}, out "
          f"{gib(ma.output_size_in_bytes)}, alias "
          f"{gib(ma.alias_size_in_bytes)}, temp "
          f"{gib(ma.temp_size_in_bytes)})", flush=True)
    return compiled


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--reference", action="store_true")
    ap.add_argument("--per-chip-batch", type=int, default=0)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(HERE, "workloads", f"{args.cell}.json")) as f:
        cell = json.load(f)
    with open(os.path.join(HERE, "configs", f"{cell['config']}.json")) as f:
        conf = json.load(f)
    if args.per_chip_batch:
        conf["per_chip_batch"] = args.per_chip_batch
    chips = int(cell["chips"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    batch, image = conf["per_chip_batch"] * chips, conf["image_size"]

    if args.reference:
        from benchmarks.lib import reference
        from byol_tpu.training.build import build_net, init_variables
        from benchmarks.drivers.train_loop import program_config
        from byol_tpu.core.config import resolve
        one = SingleDeviceSharding(topo.devices[0])
        cfg = program_config(conf, seed=0, chips=chips)
        rcfg = resolve(cfg, num_train_samples=batch, num_test_samples=batch,
                       output_size=conf["num_classes"],
                       input_shape=(image, image, 3))
        net = build_net(rcfg)
        params = jax.eval_shape(lambda k: init_variables(net, rcfg, k),
                                jax.random.PRNGKey(0))["params"]["backbone"]
        struct = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                sharding=one)
        x = jax.ShapeDtypeStruct((2 * batch, image, image, 3), jnp.float32,
                                 sharding=one)
        for keys, fn in reference.backbone_segments(
                params, image_size=image,
                vit_heads=conf.get("num_heads", 0)):
            p = jax.tree_util.tree_map(struct, reference._subset(params, keys))
            y = jax.eval_shape(lambda p_, x_: fn(p_, None, x_), p, x)
            ct = jax.ShapeDtypeStruct(y.shape, y.dtype, sharding=one)

            def bwd(p_, x_, ct_, fn=fn):
                return jax.vjp(lambda a, b: fn(a, None, b), p_, x_)[1](ct_)
            report(f"reference backward {'+'.join(keys) or 'pool'} "
                   f"in {x.shape}", jax.jit(bwd).lower(p, x, ct).compile())
            x = jax.ShapeDtypeStruct(y.shape, y.dtype, sharding=one)
        return

    from byol_tpu.core.config import resolve
    from byol_tpu.core.precision import get_policy
    from byol_tpu.parallel.compile_plan import build_plan
    from byol_tpu.parallel.mesh import AXIS_NAMES
    from byol_tpu.training.build import (build_net, build_tx,
                                         init_variables, step_config)
    from byol_tpu.training.state import create_train_state
    from byol_tpu.training.steps import make_train_step
    from benchmarks.drivers.train_loop import program_config
    cfg = program_config(conf, seed=0, chips=chips)
    rcfg = resolve(
        cfg, num_train_samples=conf["schedule"]["steps_per_epoch"] * batch,
        num_test_samples=batch, output_size=conf["num_classes"],
        input_shape=(image, image, 3))
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(chips, 1, 1),
                AXIS_NAMES)
    net = build_net(rcfg)
    tx, schedule = build_tx(rcfg)
    state = jax.eval_shape(
        lambda k: create_train_state(init_variables(net, rcfg, k), tx),
        jax.random.PRNGKey(0))
    plan = build_plan(mesh)
    step = plan.jit_train_step(
        make_train_step(net, tx, step_config(rcfg),
                        get_policy(cfg.device.half), lr_schedule=schedule,
                        mesh=mesh), plan.state_sharding(state))
    view = jax.ShapeDtypeStruct((batch, image, image, 3), jnp.float32)
    b = {"view1": view, "view2": view,
         "label": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    with mesh:
        compiled = report(f"{args.cell} train step, per-chip batch "
                          f"{conf['per_chip_batch']}, {chips} chip(s)",
                          step.lower(state, b).compile())
    text = compiled.as_text()
    import re
    kinds = ("all-reduce", "all-gather", "reduce-scatter",
             "collective-permute", "all-to-all")
    print("collectives: " + ", ".join(
        f"{k} {len(re.findall(re.escape(k) + r'(?:-start)?[(]', text))}"
        for k in kinds))

if __name__ == "__main__":
    main()
