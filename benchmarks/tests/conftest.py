"""Fixtures of the benchmark's own tests (CPU only, no chip, no libtpu).

``bench_copy`` is a copy of ``benchmarks/`` in a temporary directory to
which a tiny configuration, three cells and one per-layer metric are ADDED
as new files — no file that was there is edited.  The end-to-end tests run
``run.py --rehearse-cpu`` from it, which also shows that a later PR can add
all three by files alone.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

TIGHT_F32 = {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
             "grad_dir_gap": 1e-4, "update_norm_gap": 5e-3}


def _tiny_train_config(name, base, **changes):
    conf = json.load(open(os.path.join(BENCH, "configs", base)))
    conf.update(name=name, image_size=32, head_latent_size=64,
                projection_size=32, num_classes=10, **changes)
    flags = conf["flags"]
    for flag, key in (("--arch", "arch"), ("--image-size-override",
                                           "image_size"),
                      ("--head-latent-size", "head_latent_size"),
                      ("--projection-size", "projection_size")):
        flags[flags.index(flag) + 1] = str(conf[key])
    if conf["precision"] == "float32":
        flags.append("--no-half")
    return conf


@pytest.fixture(scope="session")
def bench_copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_copy")
    dst = os.path.join(root, "benchmarks")
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__"))
    before = {os.path.relpath(os.path.join(d, f), dst): os.path.getmtime(
        os.path.join(d, f)) for d, _, fs in os.walk(dst) for f in fs}

    def put(rel, obj):
        path = os.path.join(dst, rel)
        assert not os.path.exists(path), f"{rel} would edit an existing file"
        with open(path, "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    put("configs/tiny_rn18_f32.json", _tiny_train_config(
        "tiny_rn18_f32", "byol_rn50_224.json", arch="resnet18",
        representation_size=512, per_chip_batch=16, precision="float32"))
    put("configs/tiny_rn18_bf16_as_f32.json", _tiny_train_config(
        "tiny_rn18_bf16_as_f32", "byol_rn50_224.json", arch="resnet18",
        representation_size=512, per_chip_batch=16, precision="bfloat16"))
    put("configs/tiny_vits16_f32.json", _tiny_train_config(
        "tiny_vits16_f32", "byol_vitb16_224.json", arch="vit_s16",
        hidden_size=384, mlp_dim=1536, num_heads=6, representation_size=384,
        per_chip_batch=8, precision="float32"))
    cell = json.load(open(os.path.join(BENCH, "workloads",
                                       "rn50_train_b256.json")))
    cell["traffic"]["trace_seconds"] = 2
    cell["check"]["limits"] = TIGHT_F32
    for name, conf in (("tiny_train", "tiny_rn18_f32"),
                       ("tiny_train_bf16", "tiny_rn18_bf16_as_f32"),
                       ("tiny_vit_train", "tiny_vits16_f32")):
        put(f"workloads/{name}.json", dict(cell, name=name, config=conf))
    serve = json.load(open(os.path.join(BENCH, "workloads",
                                        "rn50_serve_closed32.json")))
    serve.update(name="tiny_serve", config="tiny_rn18_f32")
    serve["traffic"].update(clients=4, trace_seconds=2)
    serve["traffic"]["serve_config"]["max_bucket"] = 16
    serve["check"]["limits"] = {"embed_rel_gap": 1e-4}
    put("workloads/tiny_serve.json", serve)
    put("layer_metrics/train_step.last_loss.py",
        'NAME = "train_step.last_loss"\nLAYER = "train step"\n'
        'UNIT = "nats"\nMOVES = "train_images_per_s_per_chip"\n'
        'SOURCE = "program_counter"\n\n\ndef read(sources):\n'
        '    return sources["counters"].get("last_loss")\n')
    after = {rel: os.path.getmtime(os.path.join(dst, rel)) for rel in before}
    assert after == before, "an existing file was edited"
    return str(root)


def run_cell(root, cell, *, seed=7, seconds=1.5, trace=0, rehearse=True,
             script="run.py", extra=()):
    """Run one cell from the copy; returns (returncode, stdout lines)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(root, ".jax_cache"))
    argv = [sys.executable, os.path.join(root, "benchmarks", script),
            "--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), *extra]
    if rehearse:
        argv.append("--rehearse-cpu")
    proc = subprocess.run(argv, cwd=root, env=env, capture_output=True,
                          text=True, timeout=600)
    return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr
