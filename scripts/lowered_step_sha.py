#!/usr/bin/env python3
"""SHA-256 of the LOWERED train-step text of the benchmark's cells.

    JAX_PLATFORMS=cpu python3 scripts/lowered_step_sha.py [--root DIR]
        [--dump DIR] [cell ...]

The proof that a PR leaves a cell's program alone: run it on the parent
(``--root`` a ``git archive`` of it) and on the change and compare.  The
step is lowered for ONE DESCRIBED v5e chip — nothing compiles, nothing
runs, no chip — through the compile plan's ``jit_train_step``, as
``setup_training`` wires it and ``benchmarks/rehearse_v5e.py`` lowers it
(PERF.md section 6 quotes these hashes since PR 26; PR 28's hash of the
token cell, ``74f1c09e…``, was of ``rehearse_v5e_tokens.py``'s bare
``jax.jit`` instead).  Every cell is lowered with ``jax.default_backend``
patched to ``"tpu"``, so that what the ``applies`` functions of ops/ choose
is the kernel path the chip runs, as the whole-step cases of
tests/test_tpu_compile.py do: the text of a cell in ``KERNEL_CELLS`` must
hold ``tpu_custom_call``s, or the script stops.

Two hashes a cell.  ``text`` is of the text as it is.  A Pallas kernel's
serialized MLIR carries its debug locations — the checkout's PATH and the
LINE of every frame, ``training/steps.py`` among them — so for a program
with kernels ``text`` moves with any line above the call and with the
directory: compare ``no_loc``, the text with each kernel body replaced by
the hash of its MLIR printed without locations.  Without kernels the two
say the same.
"""
from __future__ import annotations

import argparse
import base64
import hashlib
import importlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
KERNEL_BODY = r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22'
# cells whose trunk runs a tiled causal kernel (ops/causal_attention.py)
KERNEL_CELLS = ("qwen3next_train_b4_s4096", "keye_train_b4_s4096",
                "lfm2_train_b4_s4096", "joyai_train_b4_s4096",
                "sdar_train_b2_s4096", "phi4flash_train_b2_s8192")


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def without_kernel_locations(text: str) -> str:
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    def body_hash(match):
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return "KERNEL<" + _sha(module.operation.get_asm(
                enable_debug_info=False)) + ">"
    return re.sub(KERNEL_BODY, body_hash, text)


def lowered_text(cell_name: str, topo) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from byol_tpu.core.config import resolve
    from byol_tpu.core.precision import get_policy
    from byol_tpu.parallel.compile_plan import build_plan
    from byol_tpu.parallel.mesh import AXIS_NAMES
    from byol_tpu.training.build import (build_net, build_tx,
                                         init_variables, step_config)
    from byol_tpu.training.state import create_train_state
    from byol_tpu.training.steps import make_train_step
    with open(f"benchmarks/workloads/{cell_name}.json") as f:
        cell = json.load(f)
    with open(f"benchmarks/configs/{cell['config']}.json") as f:
        conf = json.load(f)
    chips, tokens = int(cell["chips"]), "seq_len" in conf
    if tokens:
        from benchmarks.drivers import train_tokens
        # a driver with a depth check of its own (phi4flash's "15-19")
        program_config = getattr(
            importlib.import_module("benchmarks.drivers." + cell["driver"]),
            "program_config", train_tokens.program_config)
        from byol_tpu.models.registry import get_spec
        # a block-diffusion trunk's sample is [noised | clean]: what
        # data/loader hands the program (the parent's registry has no such)
        doubled = getattr(get_spec(conf["arch"]), "diffusion_block", 0)
        shape = (conf["seq_len"] * (2 if doubled else 1),)
    else:
        from benchmarks.drivers.train_loop import program_config
        shape = (conf["image_size"], conf["image_size"], 3)
    batch = conf["per_chip_batch"] * chips
    cfg = program_config(conf, seed=0, chips=chips)
    rcfg = resolve(
        cfg, num_train_samples=conf["schedule"]["steps_per_epoch"] * batch,
        num_test_samples=batch, output_size=conf["num_classes"],
        input_shape=shape)
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(chips, 1, 1),
                AXIS_NAMES)
    net = build_net(rcfg)
    tx, _ = build_tx(rcfg)
    state = jax.eval_shape(
        lambda k: create_train_state(init_variables(net, rcfg, k), tx),
        jax.random.PRNGKey(0))
    step = make_train_step(net, tx, step_config(rcfg),
                           get_policy(cfg.device.half), mesh=mesh)
    view = jax.ShapeDtypeStruct((batch,) + shape,
                                jnp.int32 if tokens else jnp.float32)
    views = {"view1": view, "view2": view,
             "label": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    plan = build_plan(mesh)
    with mesh:
        return plan.jit_train_step(step, plan.state_sharding(state)).lower(
            state, views).as_text()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout to lower")
    ap.add_argument("--dump", help="write each cell's text here")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    from jax.experimental import topologies
    import byol_tpu
    if os.path.dirname(os.path.dirname(byol_tpu.__file__)) != root:
        raise SystemExit(f"byol_tpu came from {byol_tpu.__file__}, not "
                         f"from {root}")
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    with open("BENCHMARK.json") as f:
        cells = args.cells or [w["name"] for w in json.load(f)["workloads"]]
    jax.default_backend = lambda: "tpu"     # what ops/*.applies ask
    for name in cells:
        text = lowered_text(name, topo)
        if name in KERNEL_CELLS and "tpu_custom_call" not in text:
            raise SystemExit(f"{name}: no kernel in the lowered step")
        if args.dump:
            with open(os.path.join(args.dump, name + ".txt"), "w") as f:
                f.write(text)
        print(f"{name} text {_sha(text)} no_loc "
              f"{_sha(without_kernel_locations(text))} bytes {len(text)}",
              flush=True)


if __name__ == "__main__":
    main()
