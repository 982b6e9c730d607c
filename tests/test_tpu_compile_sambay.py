"""Compile-for-the-chip tests of the decoder-hybrid-decoder trunk's cell
(``benchmarks/workloads/phi4flash_train_b2_s8192.json``): the selective
scan's kernel pair and differential attention's core under the band at the
published sizes, and the cell's whole train step, compiled by the TPU's own
compiler for a DESCRIBED ``v5e:2x2`` topology — no chip attached, nothing
runs.

A file of its own (tests/test_tpu_compile.py is the run's longest: ROADMAP
D9) under that file's rules: the topology is described inside a
module-scoped fixture that skips when it cannot be, everything built from it
is built inside a fixture or a test, the persistent compilation cache is off
around the compiles.  Tier-1's command allows a second process to load the
TPU's library (``ALLOW_MULTIPLE_LIBTPU_LOAD``).
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from tests.test_tpu_compile import (V5E_HBM_BYTES, _compile_train_step,
                                    _core_kernel_calls, _float32_squares,
                                    _program_bytes)
from tests.test_tpu_compile import no_persistent_cache, one_chip, topo  # noqa: F401,E501

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, SEQ, CHANNELS, STATE = 4, 8192, 5120, 16


def _state_shaped(text):
    """Arrays with a state a POSITION, ``[.., 8192, .., 5120, 16]`` in any
    order of the last two: what the kernels exist to keep out of HBM (the
    border states are a 64th of it, ``[4, 64, 16, 5120]``)."""
    return [a for a in re.findall(r"\[[\d,]+\]", text)
            if {"5120", "16"} <= set(a[1:-1].split(","))
            and any(int(n) >= SEQ for n in a[1:-1].split(","))]


def test_the_selective_scan_kernels_at_the_published_sizes(
        no_persistent_cache, one_chip):  # noqa: F811
    """``phi4flash_train_b2_s8192``'s scan — 4 rows of 8,192 positions,
    5,120 channels, 16 states, bfloat16 rows and float32 steps — forward and
    backward: one kernel each, the only arrays with a state a row's CHUNK
    the border states, no loop outside them."""
    from byol_tpu.ops import selective_scan
    like = lambda kind, *shape: jax.ShapeDtypeStruct(shape, kind,
                                                     sharding=one_chip)
    wide, f32 = jnp.bfloat16, jnp.float32

    def loss(u, delta, a, b, c):
        return jnp.sum(jnp.square(selective_scan.scan_kernels(
            u, delta, a, b, c, interpret=False).astype(f32)))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        like(wide, ROWS, SEQ, CHANNELS), like(f32, ROWS, SEQ, CHANNELS),
        like(f32, CHANNELS, STATE), like(wide, ROWS, SEQ, STATE),
        like(wide, ROWS, SEQ, STATE)).compile()
    text = compiled.as_text()
    assert _core_kernel_calls(text, "selective_scan") == [1, 1]
    assert not _state_shaped(text) and " while(" not in text
    assert "f32[4,64,16,5120]" in text                 # the border states
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 2 ** 30


def test_the_differential_core_under_the_band_at_the_published_sizes(
        no_persistent_cache, one_chip, monkeypatch):  # noqa: F811
    """The band layer's core — 40 query heads of 64 on 20 key heads, values
    128 wide, 8,192 keys under a window of 512 — lowered as on a TPU: one
    kernel each way over the 31 tile pairs the band touches, the pairs'
    bounds as scalars, no float32 score tile in HBM."""
    from byol_tpu.ops.attention import (blockwise_causal_attention,
                                        window_tiles)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tiles = window_tiles(16, 512, 512)
    assert len(tiles.q_of) == 31
    like = lambda h, d: jax.ShapeDtypeStruct((ROWS, h, SEQ, d), jnp.bfloat16,
                                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(jnp.square(blockwise_causal_attention(
            q, k, v, block=512, tiles=tiles).astype(jnp.float32)))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        like(40, 64), like(20, 64), like(20, 128)).compile().as_text()
    assert _core_kernel_calls(text, "causal_attention") == [1, 1]
    assert not _float32_squares(text) and " while(" not in text
    assert not re.search(r"\[[\d,]*8192,8192\]", text)


def test_phi4flash_train_step_fits_and_keeps_the_state_on_the_chip(
        no_persistent_cache, topo, monkeypatch):  # noqa: F811
    """``phi4flash_train_b2_s8192``'s step, lowered as on a TPU from the
    configuration file's own flags: it fits the chip within 14.0 GiB; the
    scan is ``3 selective_scan_fwd + 1 selective_scan_bwd`` kernels (target,
    online, recomputed forward; one backward) and no array outside them
    holds a state a position; the three cores are ``9 + 3`` attention
    kernels and no score tile is left in HBM; the ops carry the trunk's
    scopes and nothing routes."""
    from benchmarks.drivers.train_sambay_tokens import program_config
    from byol_tpu.core import config as config_lib
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "byol_phi4_mini_flash_vp8.json")) as f:
        conf = json.load(f)
    batch, rows = conf["per_chip_batch"], conf["seq_len"]
    rcfg = config_lib.resolve(
        program_config(conf, seed=0, chips=1),
        num_train_samples=conf["schedule"]["steps_per_epoch"] * batch,
        num_test_samples=batch, output_size=conf["num_classes"],
        input_shape=(rows,))
    compiled = _compile_train_step(
        topo, rcfg, batch, jax.ShapeDtypeStruct((batch, rows), jnp.int32))
    print(f"phi4flash step: {_program_bytes(compiled) / 2 ** 30:.2f} GiB")
    assert 4 * 2 ** 30 < _program_bytes(compiled) < 14.0 * 2 ** 30 \
        < V5E_HBM_BYTES, f"{_program_bytes(compiled) / 2 ** 30:.2f} GiB"
    text = compiled.as_text()
    assert _core_kernel_calls(text, "selective_scan") == [3, 1]
    assert _core_kernel_calls(text, "causal_attention") == [9, 3]
    assert not _state_shaped(text)
    assert not _float32_squares(text)
    assert not re.search(rf"\[[\d,]*{rows},{rows}\]", text)
    for scope in ("ssm/scan", "ssm/proj", "ssm/conv", "ssm/gate",
                  "diff/core", "diff/subln", "gmu/in_proj", "ffn/gate"):
        assert scope in text, scope
    assert "/moe/" not in text and "/gqa/" not in text
