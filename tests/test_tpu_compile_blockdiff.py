"""Compile-for-the-chip tests of the block-diffusion trunk's cell
(``benchmarks/workloads/sdar_train_b2_s4096.json``): the tiled attention
kernels under the block-diffusion list of tile pairs at the published sizes,
and the cell's whole train step, compiled by the TPU's own compiler for a
DESCRIBED ``v5e:2x2`` topology — no chip attached, nothing runs.

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


def test_the_kernels_under_the_block_diffusion_list_at_the_published_sizes(
        no_persistent_cache, one_chip, monkeypatch):  # noqa: F811
    """``sdar_train_b2_s4096``'s core — 4 rows ``[noised | clean]`` of 2 x
    4,096 positions, 32 query on 4 key/value heads of 128, blocks of 4 —
    lowered as on a TPU, forward and backward: one kernel each over the 80
    tile pairs a row, the kinds' masks made inside (a shift of two iotas: the
    TPU's compiler takes it), no float32 ``(.., 512, 512)`` array, no
    ``[.., 8192, 8192]`` one and no loop outside them."""
    from byol_tpu.ops.attention import (block_diffusion_tiles,
                                        blockwise_causal_attention)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tiles = block_diffusion_tiles(8, 4)
    assert len(tiles.q_of) == 80
    like = lambda h: jax.ShapeDtypeStruct((4, h, 8192, 128), jnp.bfloat16,
                                          sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(jnp.square(blockwise_causal_attention(
            q, k, v, block=512, tiles=tiles).astype(jnp.float32)))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        like(32), like(4), like(4)).compile().as_text()
    assert _core_kernel_calls(text, "causal_attention") == [1, 1]
    assert not _float32_squares(text) and " while(" not in text
    assert not re.search(r"\[[\d,]*8192,8192\]", text)


def test_sdar_train_step_fits_and_keeps_its_scopes(
        no_persistent_cache, topo, monkeypatch):  # noqa: F811
    """``sdar_train_b2_s4096``'s step, lowered as on a TPU from the
    configuration file's own flags with the rows ``data/loader`` hands it
    (``[noised | clean]``: twice ``--seq-len`` ids): it fits the chip within
    the 14.0 GiB its depth was chosen under, the core is ``3 N
    causal_attention_fwd + N causal_attention_bwd`` kernels (target, online,
    recomputed forward; one backward a layer), no float32 score tile and no
    ``[.., 8192, 8192]`` array is left in HBM, and the ops carry the
    ``blockdiff`` scopes."""
    from benchmarks.drivers.train_tokens import program_config
    from byol_tpu.core import config as config_lib
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "byol_sdar_30b_a3b_ep8.json")) as f:
        conf = json.load(f)
    batch, rows = conf["per_chip_batch"], 2 * conf["seq_len"]
    layers = conf["num_hidden_layers"]
    rcfg = config_lib.resolve(
        program_config(conf, seed=0, chips=1),
        num_train_samples=conf["schedule"]["steps_per_epoch"] * batch,
        num_test_samples=batch, output_size=conf["num_classes"],
        input_shape=(rows,))
    compiled = _compile_train_step(
        topo, rcfg, batch, jax.ShapeDtypeStruct((batch, rows), jnp.int32))
    print(f"sdar step: {_program_bytes(compiled) / 2 ** 30:.2f} GiB")
    assert 4 * 2 ** 30 < _program_bytes(compiled) < 14.0 * 2 ** 30 \
        < V5E_HBM_BYTES, f"{_program_bytes(compiled) / 2 ** 30:.2f} GiB"
    text = compiled.as_text()
    assert _core_kernel_calls(text, "causal_attention") == [3 * layers,
                                                            layers]
    assert not _float32_squares(text)
    assert not re.search(rf"\[[\d,]*{rows},{rows}\]", text)
    for scope in ("blockdiff/core", "blockdiff/q_norm", "moe/route",
                  "moe/experts/combine"):
        assert scope in text, scope
    assert "/gqa/" not in text and "/dsa/" not in text
