"""Compile-for-the-chip tests: the kernels of the main path, and one whole
train step, lowered with ``interpret=False`` and compiled by the TPU's own
compiler for a DESCRIBED ``v5e:2x2`` topology — no chip attached, nothing
runs.  They guard what the Pallas interpreter cannot see: PR 22 found
``fused_two_view`` refused here (no uint8 -> float32 cast; no vector layout
around a minor dimension of 3) after it had passed every interpret-mode
test.

Rules this file keeps (``on-chip-measurement`` guide, section 2): the
topology is described inside a module-scoped, non-autouse fixture that
skips when it cannot be — never at import, in a ``skipif``, in
``parametrize`` or in conftest — because only one process may load the
TPU's library, and every xdist worker imports every test file; everything
built from the topology is built inside a fixture or a test; all these
tests live in this ONE file; the compiles happen in this process; the
persistent compilation cache is off around them (such an entry cannot be
read back without a chip).

A compile that passes is not a chip run: ``python chip_smoke.py`` is.
"""
import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import SingleDeviceSharding

from byol_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, SEQUENCE_AXIS

V5E_HBM_BYTES = 16 * 2 ** 30
BATCH, IMAGE, RAW = 256, 224, 256       # the flagship's per-chip batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _flagship_rcfg(arch="resnet50", batch=BATCH):
    """ResNet-50 BYOL as byol_tpu/cli.py builds it by default, batch 256
    (or another backbone under the same heads, loss and optimizer)."""
    from byol_tpu.core import config as config_lib
    c = config_lib.Config()
    c = c.replace(
        task=dataclasses.replace(c.task, batch_size=batch, epochs=2),
        model=dataclasses.replace(c.model, arch=arch, fuse_views=True),
        device=dataclasses.replace(c.device, num_replicas=1, half=True))
    return config_lib.resolve(c, num_train_samples=4 * batch,
                              num_test_samples=batch, output_size=10,
                              input_shape=(IMAGE, IMAGE, 3))


def _with(tree, sharding):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# ---------------------------------------------------------------------------
# the fused two-view augmentation (ops/fused_augment.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("raw,dtype", [(RAW, "uint8"), (IMAGE, "uint8"),
                                       (RAW, "float32"),
                                       (IMAGE, "float32")])
def test_fused_two_view(no_persistent_cache, one_chip, raw, dtype):
    from byol_tpu.ops import fused_augment
    images = jax.ShapeDtypeStruct((BATCH, raw, raw, 3), np.dtype(dtype),
                                  sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    _compile(lambda k, im: fused_augment.fused_two_view(
        k, im, IMAGE, interpret=False), key, images)


# ---------------------------------------------------------------------------
# the decoder trunk's expert layer at the published widths: sort, ragged
# products over the held experts (the compiler's own ragged-dot kernel),
# forward and backward (models/decoder_trunk.py)
# ---------------------------------------------------------------------------

def test_expert_layer_at_published_widths(no_persistent_cache, one_chip):
    from byol_tpu.models import decoder_trunk as trunk_lib
    z = trunk_lib.XING4_29B_A4B
    layer = trunk_lib.ExpertLayer(z, 0, 8, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2, 1024, z.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    params = _with(jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0),
                           jnp.zeros(x.shape, x.dtype)))["params"], one_chip)
    assert params["experts"]["gate"].shape == (8, 3584, 1024)
    assert params["router"].shape == (3584, 64)      # all 64 are scored

    def loss(p, x):
        return jnp.sum(layer.apply({"params": p}, x).astype(jnp.float32))
    compiled = _compile(jax.grad(loss), params, x)
    # a quarter of the copies is the usual product, all of them the fallback
    assert "conditional" in compiled.as_text()


# the cells' layer calls: sizes, experts held, (sequences, tokens), and
# whether the fallback over every copy is one product (its window IS every
# copy: the ``jax.numpy`` body by shape) or slabs of the usual size
_EXPERT_LAYERS = {
    "xing4_train_b8_s1024": ("XING4_29B_A4B", 8, (16, 1024), True),
    "qwen3next_train_b4_s4096": ("QWEN3_NEXT_80B_A3B", 32, (8, 4096), False),
    "keye_train_b4_s4096": ("KEYE_VL2_30B_A3B", 16, (8, 4096), False),
}


# the fourth router width (256) and the two cells that share a width: the
# routing's rule of shapes is asked at all six trunk cells' sizes
_ROUTED_LAYERS = dict(
    _EXPERT_LAYERS,
    joyai_train_b4_s4096=("JOYAI_LLM_FLASH", 16, (8, 4096), False))


@pytest.fixture(scope="module")
def layer_texts():
    """The compiled texts of the cells' expert layers: one compile a cell."""
    return {}


def _expert_layer_text(cell, one_chip, monkeypatch, texts):
    """The compiled text of a cell's expert layer, forward and backward,
    lowered as on a TPU (the kernels' rules of shapes ask
    ``jax.default_backend()``), kept in ``texts``."""
    from byol_tpu.models import decoder_trunk as trunk_lib
    sizes, held, (batch, seq), _ = _ROUTED_LAYERS[cell]
    z = getattr(trunk_lib, sizes)
    if cell not in texts:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        layer = trunk_lib.ExpertLayer(z, 0, held, jnp.bfloat16)
        x = jax.ShapeDtypeStruct((batch, seq, z.hidden_size), jnp.bfloat16,
                                 sharding=one_chip)
        params = _with(jax.eval_shape(
            lambda: layer.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8, z.hidden_size), x.dtype))
        )["params"], one_chip)

        def loss(p, x):           # not linear: the forward's combine stays
            return jnp.sum(jnp.square(
                layer.apply({"params": p}, x).astype(jnp.float32)))
        texts[cell] = _compile(
            jax.grad(loss, argnums=(0, 1)), params, x).as_text()
    return z, texts[cell]


def _route_rows(text):
    """The instructions under ``moe/route`` — in the layer's text the scope
    ``route`` — fused ones too: (opcode, result type, operands, path)."""
    import re
    rows = []
    for line in text.splitlines():
        found = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([\w-]+)\((.*)$", line)
        path = re.search(r'op_name="([^"]*)"', line)
        if found and path and "/route/" in path.group(1):
            rows.append((found.group(2), found.group(1), found.group(3),
                         path.group(1)))
    return rows


def _elements(kind):
    import re
    return [int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(r"\b[a-z]+\d+\[([\d,]*)\]", kind)]


@pytest.mark.parametrize("cell", sorted(_ROUTED_LAYERS))
def test_expert_layer_routes_without_a_sort(no_persistent_cache, one_chip,
                                            monkeypatch, layer_texts, cell):
    """At each router width (64, 128, 256, 512), forward and backward: under
    ``route`` no ``sort``, and no ``gather`` / ``scatter`` that reads,
    writes or is indexed by ``tokens x k`` or ``tokens x E`` elements; the
    choices, the tables and ``weight_of``'s backward are one kernel each
    (PERF.md section 6, PR 46)."""
    import re
    from byol_tpu.ops import expert_routing
    _, held, (batch, seq), _ = _ROUTED_LAYERS[cell]
    z, text = _expert_layer_text(cell, one_chip, monkeypatch, layer_texts)
    tokens, k, experts = batch * seq, z.num_experts_per_tok, \
        z.n_routed_experts
    assert expert_routing.applies(tokens, experts, k, held, backend="tpu")
    rows = _route_rows(text)
    assert len(rows) > 10
    assert not [row for row in rows if row[0] == "sort"]
    large = {tokens * k, tokens * experts}
    assert not [row for row in rows if row[0] in ("gather", "scatter")
                and large & set(_elements(row[1]) + _elements(row[2]))]
    for kernel, calls in (("route_choose", 1), ("route_tables", 1),
                          ("route_tables_bwd", 1)):
        assert len(re.findall(
            rf"custom-call\([^\n]*/{kernel}/pallas_call", text)) == calls
    for scope in ("route/choose/", "route/tables/"):
        assert scope in text


@pytest.mark.parametrize("backend,sizes", [
    ("cpu", (32768, 512, 10, 32)),               # not lowered for a TPU
    ("tpu", (32768 + 512, 512, 10, 32)),         # no whole tile of tokens
    ("tpu", (32768, 96, 4, 8)),                  # a router of 96
    ("tpu", (2 ** 20, 512, 10, 64)),             # more than VMEM takes
])
def test_expert_routing_falls_back_to_jax_numpy(no_persistent_cache, one_chip,
                                                monkeypatch, backend, sizes):
    """Another backend and shapes the kernels do not take keep ``top_k`` and
    ``argsort`` (no kernel in the text), at a small size of the same kind."""
    from byol_tpu.ops import expert_routing
    assert not expert_routing.applies(*sizes, backend=backend)
    assert expert_routing.applies(32768, 512, 10, 32, backend="tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    tokens, experts, k, held = 1024 + sizes[0] % 1024, sizes[1], 4, 8
    if sizes[0] == 2 ** 20:
        monkeypatch.setattr(expert_routing, "VMEM_LIMIT_BYTES", 2 ** 16)

    def route(select):
        kernel = expert_routing.applies(tokens, experts, k, held)
        weight, chosen = expert_routing.choose(select, None, k,
                                               kernel=kernel)
        return expert_routing.tables(chosen, weight, 0, held, kernel=kernel)
    text = jax.jit(route).lower(jax.ShapeDtypeStruct(
        (tokens, experts), jnp.float32, sharding=one_chip)
    ).compile().as_text()
    assert "tpu_custom_call" not in text and " sort(" in text


@pytest.mark.parametrize("cell", sorted(_EXPERT_LAYERS))
def test_expert_layer_combine_moves_no_relaid_out_copies(
        no_persistent_cache, one_chip, monkeypatch, layer_texts, cell):
    """At the three trunk cells' shapes, lowered as on a TPU
    (``sum_copies.applies`` asks ``jax.default_backend()``): the combine and
    the dispatch's backward of a window of ``cap`` rows are each ONE gather
    of ``[cap, D]`` into token order and one ``sum_copies`` kernel — no
    gather a slot of ``[tokens, D]``, no array of ``tokens x k x D`` elements
    under the ``combine`` scope, no ``copy`` / ``reshape`` of the hidden
    states (PERF.md section 6, PR 30 and 37).  Where the fallback is one
    product over every copy, that branch keeps the k gathers a sum."""
    _, held, (batch, seq), whole_fallback = _EXPERT_LAYERS[cell]
    z, text = _expert_layer_text(cell, one_chip, monkeypatch, layer_texts)
    tokens, k, d = batch * seq, z.num_experts_per_tok, z.hidden_size
    cap = 2 * tokens * k * held // z.n_routed_experts
    assert f"[{tokens},{k},{d}]" not in text
    assert f"[{k},{tokens},{d}]" not in text
    # the ops that read and write HBM on their own, under the scope the
    # trace reads: (name, result bytes, opcode, operands, path, called)
    from scripts import hlo_bytes_by_scope
    combine = [row for name, rows in hlo_bytes_by_scope.parse(text).items()
               if name and not name.startswith("%fused_computation")
               for row in rows if "/combine/" in row[4]]
    assert max(row[1] for row in combine) == max(cap, tokens) * d * 2
    assert not [row for row in combine if row[2] in ("copy", "reshape")]
    # forward combine and dispatch backward of the usual window, and of a
    # slab where the fallback runs in slabs: a kernel and a gather each
    windows = 2 if whole_fallback else 4
    assert len([row for row in combine if row[2] == "custom-call"
                and "sum_copies" in row[4]]) == windows
    gathers = collections.Counter(
        row[1] // (d * 2) for row in combine
        if row[2] == "fusion" and row[4].endswith("/gather")
        and row[1] % (d * 2) == 0 and row[1] >= tokens * d * 2)
    want = collections.Counter({cap: windows})
    if whole_fallback:        # (xing4's window is as long as its tokens)
        want[tokens] += 2 * k
    assert gathers == want


# ---------------------------------------------------------------------------
# one whole ResNet-50 train step at batch 256 fits the chip
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# the patterned trunk's two mixers at the published head sizes and 4,096
# tokens, forward and backward: attention that never writes [S, S]
# (ops/attention.blockwise_causal_attention) and the delta rule in chunks
# (models/gated_delta.py)
# ---------------------------------------------------------------------------

def test_blockwise_causal_attention_writes_no_square_at_4096(
        no_persistent_cache, one_chip):
    from byol_tpu.ops.attention import blockwise_causal_attention
    seq = 4096
    q = jax.ShapeDtypeStruct((1, 16, seq, 256), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2, seq, 256), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(jnp.square(blockwise_causal_attention(
            q, k, v, block=512).astype(jnp.float32)))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()            # plain jax.numpy: no kernel
    assert f"{seq},{seq}]" not in text            # no [.., S, S] anywhere
    assert ",512,512]" in text                    # tiles of one block pair


def _causal_core_text(one_chip, monkeypatch, *, backend, heads, kv_heads,
                      dim, seq=4096, block=512, batch=8):
    """The causal core, forward and backward, compiled for the described v5e
    as ``backend`` would lower it, a cell's whole batch a call."""
    from byol_tpu.ops.attention import blockwise_causal_attention
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    like = lambda h: jax.ShapeDtypeStruct((batch, h, seq, dim), jnp.bfloat16,
                                          sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(jnp.square(blockwise_causal_attention(
            q, k, v, block=block, group=2).astype(jnp.float32)))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        like(heads), like(kv_heads), like(kv_heads)).compile().as_text()


def _float32_squares(text, block=512):
    """Float32 ``[.., block, block]`` arrays of three dimensions or more: a
    score tile has a head in front (a plain ``f32[512,512]`` is the
    compiler's slice of a ``[2048, 512]`` projection)."""
    import re
    return re.findall(rf"f32\[[\d,]+,{block},{block}\]", text)


@pytest.mark.parametrize("heads,kv_heads,dim", [
    (16, 2, 256),       # qwen3next_train_b4_s4096: (8, 16, 4096, 256)
    (32, 8, 64),        # lfm2_train_b4_s4096: (8, 32, 4096, 64)
    (32, 4, 128)])
def test_causal_attention_kernels_at_the_published_sizes(
        no_persistent_cache, one_chip, monkeypatch, heads, kv_heads, dim):
    """On a TPU the core is ``causal_attention_fwd`` and
    ``causal_attention_bwd`` over all 8 sequences at once: no float32
    ``(.., 512, 512)`` array and no loop is left outside them."""
    text = _causal_core_text(one_chip, monkeypatch, backend="tpu",
                             heads=heads, kv_heads=kv_heads, dim=dim)
    assert _core_kernel_calls(text, "causal_attention") == [1, 1]
    assert not _float32_squares(text)
    assert " while(" not in text


@pytest.mark.parametrize("dim,rope", [
    (128, 64),     # two products a tile, the rotary key [B, S, 64] once
    (256, 0),      # the 192 of a head padded to two lane tiles
])
def test_latent_attention_core_at_the_published_sizes(
        no_persistent_cache, one_chip, monkeypatch, dim, rope):
    """``joyai_train_b4_s4096``'s core — 8 sequences x 32 heads x 4,096 keys,
    192-wide keys on 128-wide values, one head a key head — lowered as on a
    TPU, forward and backward with the shared key's gradient summed over
    the heads in the kernel: one kernel each, no float32 ``(.., 512, 512)``
    array and no loop outside them; a 192-wide operand as it stands is not
    one the kernels take.  The TPU's compiler takes them at the key heads a
    program ``key_heads`` gives from its VMEM count — FOUR forward, TWO
    backward: a count that is too optimistic fails here, not on the chip."""
    import re
    from byol_tpu.ops import causal_attention
    from byol_tpu.ops.attention import blockwise_causal_attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    like = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                               sharding=one_chip)
    heads = lambda width: like(8, 32, 4096, width)
    shared = (heads(rope), like(8, 4096, rope)) if rope else None

    def loss(q, k, v, shared):
        return jnp.sum(jnp.square(blockwise_causal_attention(
            q, k, v, block=512, shared=shared).astype(jnp.float32)))
    grad = jax.grad(loss, argnums=(0, 1, 2, 3) if rope else (0, 1, 2))
    operands = heads(dim), heads(dim), heads(128), shared
    text = jax.jit(grad).lower(*operands).compile().as_text()
    assert _core_kernel_calls(text, "causal_attention") == [1, 1]
    assert not _float32_squares(text) and " while(" not in text
    assert not causal_attention.applies(512, 192, 4096, 32, 32, vdim=128)
    held = [causal_attention.key_heads(512, dim, 4096, 1, 32, 2, forward,
                                       vdim=128, shared=rope)
            for forward in (True, False)]
    assert held == [4, 2]       # at either form of the 192-wide key
    # ... and those are the programs that compiled: (8, 32 / n, 36 pairs)
    assert set(re.findall(r"grid=\((\d+), (\d+), 36\)", str(
        jax.make_jaxpr(grad)(*operands)))) == {
            ("8", str(32 // n)) for n in held}


@pytest.mark.parametrize("backend,sizes", [
    ("cpu", dict(heads=32, kv_heads=8, dim=64)),  # not lowered for a TPU
    ("tpu", dict(heads=32, kv_heads=8, dim=96)),  # 3/4 of a lane tile a head
])                  # (at 2,048 tokens: 10 unrolled block pairs compile sooner)
def test_causal_attention_falls_back_to_jax_numpy(
        no_persistent_cache, one_chip, monkeypatch, backend, sizes):
    """Another backend and shapes the kernels do not take run the
    ``jax.numpy`` body, two sequences a pass: no kernel in the text."""
    text = _causal_core_text(one_chip, monkeypatch, backend=backend,
                             seq=2048, batch=4, **sizes)
    assert _core_kernel_calls(text, "causal_attention") == [0, 0]
    assert "tpu_custom_call" not in text and _float32_squares(text)


def test_hybrid_attention_layer_keeps_its_tiles_on_chip(
        no_persistent_cache, one_chip, monkeypatch):
    """``qwen3next_train_b4_s4096``'s attention layer (16 query on 2 key
    heads of 256, an output gate) at the cell's 8 sequences, lowered as on a
    TPU, forward and backward: one kernel each under ``core``, no float32
    ``(.., 512, 512)`` array anywhere in the layer."""
    from byol_tpu.models import decoder_trunk
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    z = decoder_trunk.QWEN3_NEXT_80B_A3B
    layer = decoder_trunk.GatedAttention(
        z.gated_attention, heads=16, kv_heads=2, eps=z.rms_norm_eps,
        dtype=jnp.bfloat16, zero_centred=True)
    h = jax.ShapeDtypeStruct((8, 4096, z.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                            jax.ShapeDtypeStruct((1, 512, z.hidden_size),
                                                 jnp.bfloat16))
    params = _with(params, one_chip)

    def loss(params, h):
        return jnp.sum(jnp.square(layer.apply(params, h).astype(
            jnp.float32)))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, h).compile().as_text()
    assert _core_kernel_calls(text, "causal_attention") == [1, 1]
    assert not _float32_squares(text)
    assert "core" in text and " while(" not in text


def test_chunked_delta_rule_scans_chunks_not_tokens(no_persistent_cache,
                                                    one_chip):
    from byol_tpu.models.gated_delta import chunked_delta_rule
    seq, heads = 4096, 32
    wide = jax.ShapeDtypeStruct((2, seq, heads, 128), jnp.bfloat16,
                                sharding=one_chip)
    gate = jax.ShapeDtypeStruct((2, seq, heads), jnp.float32,
                                sharding=one_chip)

    def loss(q, k, v, g, beta):
        return jnp.sum(jnp.square(chunked_delta_rule(
            q, k, v, g, beta, chunk=64, dtype=jnp.bfloat16,
            group=1).astype(jnp.float32)))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        wide, wide, wide, gate, gate).compile().as_text()
    from scripts import hlo_bytes_by_scope
    bounds = hlo_bytes_by_scope.loop_bounds(hlo_bytes_by_scope.parse(text))
    # the chunk scan (64 trips, forward and backward) and the map over the
    # two sequences; nothing counts to 4,096
    assert bounds and max(bounds) == seq // 64


@pytest.fixture(scope="module")
def gdn_texts():
    """The compiled texts of the rule and of the Gated DeltaNet layer: one
    compile for the tests that read the same program."""
    return {}


def _rule_text(one_chip, monkeypatch, *, backend, texts=None, **sizes):
    """The rule, forward and backward, compiled for the described v5e as
    ``backend`` would lower it (``chunked_delta_rule`` and the kernels'
    ``interpret`` default both ask ``jax.default_backend()``); kept in
    ``texts`` where one is given."""
    key = ("rule", backend) + tuple(sorted(sizes.items()))
    texts = {} if texts is None else texts
    if key not in texts:
        texts[key] = _compile_rule(one_chip, monkeypatch, backend, **sizes)
    return texts[key]


def _compile_rule(one_chip, monkeypatch, backend, chunk=128, heads=32,
                  key_heads=16, dk=128, dv=128, seq=4096):
    from byol_tpu.models.gated_delta import chunked_delta_rule
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    like = lambda *shape, kind=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, kind, sharding=one_chip)
    gate = like(2, seq, heads, kind=jnp.float32)

    def loss(q, k, v, g, beta):
        return jnp.sum(jnp.square(chunked_delta_rule(
            q, k, v, g, beta, chunk=chunk, dtype=jnp.bfloat16,
            group=1).astype(jnp.float32)))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        like(2, seq, key_heads, dk), like(2, seq, key_heads, dk),
        like(2, seq, heads, dv), gate, gate).compile().as_text()


def test_delta_rule_kernels_at_the_published_sizes(no_persistent_cache,
                                                   one_chip, monkeypatch,
                                                   gdn_texts):
    """``[2, 4096, 32, 128]`` values on 16 key heads, chunk 128, bf16: the
    within-chunk stage is ``delta_wy_fwd`` (the forward, and again under the
    rule's own checkpoint) and ``delta_wy_bwd``; no triangular solve."""
    text = _rule_text(one_chip, monkeypatch, backend="tpu", texts=gdn_texts)
    assert text.count("delta_wy_fwd") >= 2 and "delta_wy_bwd" in text
    assert "InvertDiagBlocks" not in text
    assert "triangular" not in text.lower()


def _kernel_calls(text, name):
    import re
    return len(re.findall(rf"custom-call\([^\n]*/{name}/pallas_call", text))


def test_delta_rule_scans_the_chunks_in_a_kernel_pair(no_persistent_cache,
                                                      one_chip, monkeypatch,
                                                      gdn_texts):
    """At the published sizes the recurrence between chunks is
    ``delta_scan_fwd`` (the forward, and again under the rule's own
    checkpoint, where it keeps the states) and ONE ``delta_scan_bwd``: no
    loop of ``S / C`` = 32 trips (the ``lax.map`` over the two groups
    alone), and no float32 ``[.., 128, 128]`` state written into a stacked
    array trip by trip, as the scan's autodiff kept it."""
    import re
    from scripts import hlo_bytes_by_scope
    text = _rule_text(one_chip, monkeypatch, backend="tpu", texts=gdn_texts)
    assert _kernel_calls(text, "delta_scan_fwd") >= 2
    assert _kernel_calls(text, "delta_scan_bwd") == 1
    bounds = hlo_bytes_by_scope.loop_bounds(hlo_bytes_by_scope.parse(text))
    assert bounds and set(bounds) == {2}, bounds
    assert not re.search(
        r"= f32\[[\d,]*128,128\]\S* dynamic-update-slice\(", text)


def test_delta_rule_keeps_its_squares_in_the_kernels(no_persistent_cache,
                                                     one_chip, monkeypatch):
    """Value heads of 256, so that a float32 ``[.., 128, 128]`` can only be
    a chunk's ``C x C`` matrix: whole ``[N, B, H, C, C]`` float32 arrays
    are the kernels' own (the inverse, kept from the forward for the
    backward) and nothing else computes on one."""
    import re
    text = _rule_text(one_chip, monkeypatch, backend="tpu", heads=8,
                      key_heads=4, dv=256)
    square = re.compile(r"f32\[\d+,\d+,\d+,128,128\]")
    plumbing = re.compile(
        r" (get-tuple-element|tuple|parameter|bitcast|while|conditional)\(")
    held = [line for line in text.splitlines()
            if " = " in line and square.search(line)
            and "delta_wy" not in line and not plumbing.search(line)]
    assert not held, held[:3]
    assert "delta_wy_bwd" in text


@pytest.mark.parametrize("backend,sizes", [
    ("cpu", {}),                                  # not lowered for a TPU
    ("tpu", dict(chunk=8, heads=4, key_heads=2, dk=8, dv=8, seq=64)),
    ("tpu", dict(chunk=64)),                      # half a tile of tokens
])
def test_delta_rule_falls_back_to_jax_numpy(no_persistent_cache, one_chip,
                                            monkeypatch, backend, sizes):
    """``HYBRID_TINY``'s sizes (chunk 8, heads of 8) and a lowering for
    another backend take the ``jax.numpy`` path: no kernel in the text."""
    text = _rule_text(one_chip, monkeypatch, backend=backend, **sizes)
    assert "delta_wy" not in text and "tpu_custom_call" not in text


def _gdn_layer_text(one_chip, monkeypatch, *, width, texts=None):
    """``qwen3next_train_b4_s4096``'s Gated DeltaNet layer (16 key and 32
    value heads, four taps, chunk 128) at heads ``width`` wide over ``[2,
    4096, 2048]`` in bf16, lowered as on a TPU, forward and backward; kept
    in ``texts`` where one is given."""
    texts = {} if texts is None else texts
    if ("layer", width) not in texts:
        texts["layer", width] = _compile_gdn_layer(one_chip, monkeypatch,
                                                   width)
    return texts["layer", width]


def _compile_gdn_layer(one_chip, monkeypatch, width):
    from byol_tpu.models import decoder_trunk, gated_delta
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    z = decoder_trunk.QWEN3_NEXT_80B_A3B
    sizes = dataclasses.replace(z.gated_delta, key_head_dim=width,
                                value_head_dim=width)
    layer = gated_delta.GatedDeltaNet(
        sizes, sizes.num_key_heads, sizes.num_value_heads, z.rms_norm_eps,
        jnp.bfloat16)
    h = jax.ShapeDtypeStruct((2, 4096, z.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    params = _with(jax.eval_shape(
        layer.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 128, z.hidden_size), jnp.bfloat16)),
        one_chip)

    def loss(params, h):
        return jnp.sum(jnp.square(layer.apply(params, h).astype(
            jnp.float32)))
    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, h).compile().as_text()


_GDN_PASSES = ("conv_silu_fwd", "conv_silu_bwd", "gated_norm_fwd",
               "gated_norm_bwd")


def _float32_stage_arrays(text):
    """Instructions under ``conv`` or ``gate_norm`` that are not the kernels
    and compute on a float32 array of the stage's size."""
    import re
    whole = re.compile(r"f32\[\d+,4096,(8192|4096|32,128)\]")
    return [line for line in text.splitlines()
            if " = " in line and whole.search(line.split(" = ", 1)[1])
            and re.search(r'op_name="[^"]*/(conv|gate_norm)/', line)
            and "custom-call(" not in line]


def test_gdn_elementwise_stages_are_one_kernel_each(no_persistent_cache,
                                                    one_chip, monkeypatch,
                                                    gdn_texts):
    """At the published widths the convolution with its SiLU and the gated
    norm are one kernel forward and one backward; no padded copy of the
    convolution's input and no float32 array of a stage's size outside
    them; and they read their columns out of the ONE product ``[q | k | v |
    z]``: no activation is ever held per key head, ``[.., 16, 768]``."""
    import re
    text = _gdn_layer_text(one_chip, monkeypatch, width=128, texts=gdn_texts)
    calls = [len(re.findall(rf"custom-call\([^\n]*{name}", text))
             for name in _GDN_PASSES]
    assert calls == [1, 1, 1, 1], calls
    assert not re.search(r"\[\d+,4099,8192\]", text)
    assert not _float32_stage_arrays(text), _float32_stage_arrays(text)[:3]
    assert "bf16[2,4096,12288]" in text
    assert not re.search(r"\[(4096,\d+|\d+,4096),16,768\]", text)


def _reads_straight_from(text, reader, writer):
    """Whether the ONE call of the kernel ``reader`` takes an operand that
    IS a result of the kernel ``writer``: nothing on the way but
    ``get-tuple-element`` and ``bitcast`` — no ``copy``, ``transpose`` or
    fusion lays the array out again."""
    from scripts import hlo_bytes_by_scope
    comps = hlo_bytes_by_scope.parse(text)
    rows = {row[0]: row for row in comps[comps[None]]}
    is_call = lambda row, kernel: (row[2] == "custom-call"
                                   and f"/{kernel}/pallas_call" in row[4])
    (call,) = [row for row in rows.values() if is_call(row, reader)]
    for name in call[3]:
        while rows[name][2] in ("get-tuple-element", "bitcast"):
            name = rows[name][3][0]
        if is_call(rows[name], writer):
            return True
    return False


def test_gdn_rule_output_meets_the_norm_where_it_lies(no_persistent_cache,
                                                      one_chip, monkeypatch,
                                                      gdn_texts):
    """``delta_scan_fwd`` writes ``o`` as column blocks of ``[B, S, H d_v]``
    and ``gated_norm_fwd`` reads that array itself; in the backward
    ``delta_scan_bwd`` reads ``gated_norm_bwd``'s ``d_out`` itself: NO
    ``transpose`` or ``copy`` of the rule's output between the two, in
    either direction (the scan's ``[N, B, H, C, d]`` turned there in bf16,
    7.4 ms a step: PERF.md section 5, PR 47)."""
    text = _gdn_layer_text(one_chip, monkeypatch, width=128, texts=gdn_texts)
    assert _reads_straight_from(text, "gated_norm_fwd", "delta_scan_fwd")
    assert _reads_straight_from(text, "delta_scan_bwd", "gated_norm_bwd")
    # the reader sees a copy where there is one: the convolution's result
    # is cut and normalised on its way to ``delta_wy_fwd``
    assert not _reads_straight_from(text, "delta_wy_fwd", "conv_silu_fwd")


def test_gdn_elementwise_stages_fall_back_to_jax_numpy(no_persistent_cache,
                                                       one_chip, monkeypatch):
    """Heads 64 wide half-fill a lane tile: the ``jax.numpy`` bodies, whose
    float32 arrays the check above does see — on column ranges of the same
    ONE product, no activation per key head here either."""
    import re
    text = _gdn_layer_text(one_chip, monkeypatch, width=64)
    assert not any(name in text for name in _GDN_PASSES)
    assert _float32_stage_arrays(text)
    assert "bf16[2,4096,6144]" in text
    assert not re.search(r"\[(4096,\d+|\d+,4096),16,384\]", text)


def _core_text(one_chip, monkeypatch, *, backend, block=512, dim=128,
               seq=4096):
    """Sparse attention's core, forward and backward, compiled for the
    described v5e as ``backend`` would lower it: 8 sequences, 32 query heads
    on 4 key heads — the published sizes of ``keye_train_b4_s4096``."""
    from byol_tpu.ops.attention import selected_attention
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    like = lambda *shape, kind=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, kind, sharding=one_chip)
    blocks = seq // block

    def loss(q, k, v, selected):
        out, lse = selected_attention(q, k, v, selected, block=block)
        return jnp.sum(jnp.square(out.astype(jnp.float32))), lse
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True)).lower(
        like(8, 32, seq, dim), like(8, 4, seq, dim), like(8, 4, seq, dim),
        like(blocks * (blocks + 1) // 2, 8, block, block, kind=jnp.bool_)
    ).compile().as_text()


def _core_kernel_calls(text, stem="selected_attention"):
    """Custom calls of the forward and of the backward kernel (a frame of
    the text's metadata may hold either name too)."""
    import re
    return [len(re.findall(rf"custom-call\([^\n]*{stem}_{way}", text))
            for way in ("fwd", "bwd")]


@pytest.mark.parametrize("dim", [128, 64])
def test_selected_attention_kernels_at_the_published_sizes(
        no_persistent_cache, one_chip, monkeypatch, dim):
    """The core is ``selected_attention_fwd`` and ``selected_attention_bwd``;
    no float32 ``(8, 4, 8, 512, 512)`` tile, nor any ``(.., 512, 512)``
    float32 array, is left in the program.  128 is keye's head; 64, half a
    lane tile, is what the kernels' one rule of widths admits under a
    selection too since ISSUE 44 (lfm2's head: it fell back before)."""
    import re
    text = _core_text(one_chip, monkeypatch, backend="tpu", dim=dim)
    assert _core_kernel_calls(text) == [1, 1]
    assert not re.search(r"f32\[[\d,]*512,512\]", text)
    assert " while(" not in text


@pytest.mark.parametrize("backend,sizes", [
    ("cpu", {}),                                  # not lowered for a TPU
    ("tpu", dict(block=96, seq=4032)),            # 3/4 of a lane tile
])
def test_selected_attention_falls_back_to_jax_numpy(
        no_persistent_cache, one_chip, monkeypatch, backend, sizes):
    """Another backend and shapes the kernels do not take run the
    ``jax.numpy`` body under its ``lax`` loops: no kernel in the text."""
    text = _core_text(one_chip, monkeypatch, backend=backend, **sizes)
    assert _core_kernel_calls(text) == [0, 0]
    assert "tpu_custom_call" not in text and " while(" in text


def _select_text(one_chip, monkeypatch, *, backend, block=512, seq=4096,
                 topk=2048):
    """``select_top_keys`` over the tiles of 8 sequences' index scores,
    compiled for the described v5e as ``backend`` would lower it — the
    published sizes of ``keye_train_b4_s4096``."""
    from byol_tpu.ops import key_selection
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    blocks = seq // block
    return jax.jit(lambda scores: key_selection.select_top_keys(
        scores, topk, block=block)).lower(jax.ShapeDtypeStruct(
            (blocks * (blocks + 1) // 2, 8, block, block), jnp.float32,
            sharding=one_chip)).compile().as_text()


@pytest.mark.parametrize("seq", [4096, 8192])     # rows of 8 and of 16 tiles
def test_top_keys_search_kernel_at_the_published_sizes(
        no_persistent_cache, one_chip, monkeypatch, seq):
    """The search, ties and all, is ONE ``top_keys_search`` kernel: no loop
    and no branch is left in the program, and no uint32 or int32 array of
    the tiles' size (the ordered bits live and die in VMEM)."""
    import re
    text = _select_text(one_chip, monkeypatch, backend="tpu", seq=seq)
    assert len(re.findall(r"custom-call\([^\n]*top_keys_search", text)) == 1
    assert " while(" not in text and " conditional(" not in text
    assert not re.search(r"[us]32\[[\d,]*512,512\]", text)


@pytest.mark.parametrize("backend,sizes", [
    ("cpu", {}),                                  # not lowered for a TPU
    ("tpu", dict(block=96, seq=4032, topk=2016)),     # 3/4 of a lane tile
])
def test_top_keys_search_falls_back_to_jax_numpy(
        no_persistent_cache, one_chip, monkeypatch, backend, sizes):
    """Another backend and a block the kernel does not take run the
    ``fori_loop`` of 32 trips: no kernel in the text."""
    text = _select_text(one_chip, monkeypatch, backend=backend, **sizes)
    assert "tpu_custom_call" not in text and " while(" in text


def _compile_train_step(topo, rcfg, batch, view=None):
    """The jitted step (``--fuse-views``, bf16, LARS), built from the
    compile plan exactly as setup_training wires it, compiled for one
    described chip; ``view``: one view's struct where it is no image."""
    from byol_tpu.core.precision import get_policy
    from byol_tpu.parallel.compile_plan import build_plan
    from byol_tpu.training.build import (build_net, build_tx,
                                         init_variables, step_config)
    from byol_tpu.training.state import create_train_state
    from byol_tpu.training.steps import make_train_step
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1, 1),
                (DATA_AXIS, SEQUENCE_AXIS, MODEL_AXIS))
    net = build_net(rcfg)
    tx, _ = build_tx(rcfg)
    state = jax.eval_shape(
        lambda k: create_train_state(init_variables(net, rcfg, k), tx),
        jax.random.PRNGKey(0))
    plan = build_plan(mesh)
    step = plan.jit_train_step(
        make_train_step(net, tx, step_config(rcfg), get_policy(True),
                        mesh=mesh),
        plan.state_sharding(state))
    if view is None:
        view = jax.ShapeDtypeStruct((batch, IMAGE, IMAGE, 3), jnp.float32)
    views = {"view1": view, "view2": view,
             "label": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    with mesh:
        return step.lower(state, views).compile()


def _program_bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def test_resnet50_train_step_fits_16gb(no_persistent_cache, topo):
    """The flagship's step compiles for one described chip and its
    ``memory_analysis()`` fits 16 GB."""
    total = _program_bytes(_compile_train_step(topo, _flagship_rcfg(),
                                               BATCH))
    assert 0 < total < V5E_HBM_BYTES, f"{total / 2 ** 30:.2f} GiB"


# ---------------------------------------------------------------------------
# the fused attention kernels over the packed qkv (ops/packed_attention.py)
# at ViT-B/16's shapes, and the whole ViT-B/16 step that calls them
# ---------------------------------------------------------------------------

VIT_TOKENS, VIT_HEADS = 197, 12


@pytest.mark.parametrize("batch,tokens,heads,dtype", [
    (128, VIT_TOKENS, VIT_HEADS, "bfloat16"),   # vitb16_train_b64, two views
    (128, VIT_TOKENS, VIT_HEADS, "float32"),
    (32, 512, 16, "bfloat16"),                  # the longest it takes, ViT-L
])
def test_packed_attention_forward_and_backward(no_persistent_cache, one_chip,
                                               batch, tokens, heads, dtype):
    from byol_tpu.ops.packed_attention import packed_self_attention
    qkv = jax.ShapeDtypeStruct((batch, tokens, 3 * heads * 64),
                               jnp.dtype(dtype), sharding=one_chip)
    _compile(lambda x: packed_self_attention(x, heads, interpret=False), qkv)
    compiled = _compile(jax.grad(lambda x: jnp.sum(packed_self_attention(
        x, heads, interpret=False).astype(jnp.float32))), qkv)
    assert "packed_attention_bwd" in compiled.as_text()


def test_vitb16_train_step_keeps_attention_on_chip(no_persistent_cache, topo,
                                                   monkeypatch):
    """``vitb16_train_b64``'s step, lowered as on a TPU (the rule and the
    kernels' ``interpret`` default both ask ``jax.default_backend()``, which
    is the CPU here): 36 kernel calls, no ``[..., 197, 197]`` array, and
    next to none of the 148 relayout copies the einsum form compiled to."""
    import re
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _compile_train_step(topo, _flagship_rcfg("vit_b16", 64), 64)
    text = compiled.as_text()
    assert not re.findall(r"\[[\d,]*197,197\]", text)
    entry = text[text.index("ENTRY"):]
    assert entry.count('custom_call_target="tpu_custom_call"') == 36
    assert len(re.findall(r" copy\(", entry)) <= 8
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert cost["bytes accessed"] < 115e9, cost["bytes accessed"]
    assert 0 < _program_bytes(compiled) < 7 * 2 ** 30


# ---------------------------------------------------------------------------
# the short-convolution trunk's whole step at its cell's sizes
# (benchmarks/workloads/lfm2_train_b4_s4096.json): what the chip run relies on
# ---------------------------------------------------------------------------

def test_lfm2_train_step_fits_and_keeps_its_scopes(no_persistent_cache, topo,
                                                   monkeypatch):
    """``lfm2_train_b4_s4096``'s step, lowered as on a TPU
    (``sum_copies.applies`` asks ``jax.default_backend()``) from the
    configuration file's own flags: it fits the chip, the four routing
    layers' combines are 12 ``sum_copies`` kernels (target, online, dispatch
    backward; the recomputed forward's feeds no gradient) and as many in the
    fallback's slabs, which no usual step runs; no ``[.., S, S]`` array is
    written, the attention layer's core is 3 ``causal_attention_fwd`` + 1
    ``causal_attention_bwd`` kernels and no float32 score tile, of 2
    sequences (``GatedAttentionSizes.group``, the ``jax.numpy`` lowering's)
    or of 8, is left in HBM, and the ops carry the ``shortconv`` scopes."""
    import json
    import os
    import re
    from benchmarks.drivers.train_tokens import program_config
    from byol_tpu.core import config as config_lib
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "byol_lfm2_24b_a2b_ep8.json")) as f:
        conf = json.load(f)
    batch, seq = conf["per_chip_batch"], conf["seq_len"]
    rcfg = config_lib.resolve(
        program_config(conf, seed=0, chips=1),
        num_train_samples=conf["schedule"]["steps_per_epoch"] * batch,
        num_test_samples=batch, output_size=conf["num_classes"],
        input_shape=(seq,))
    compiled = _compile_train_step(
        topo, rcfg, batch, jax.ShapeDtypeStruct((batch, seq), jnp.int32))
    print(f"lfm2 step: {_program_bytes(compiled) / 2 ** 30:.2f} GiB")
    assert 4 * 2 ** 30 < _program_bytes(compiled) < V5E_HBM_BYTES, \
        f"{_program_bytes(compiled) / 2 ** 30:.2f} GiB"
    text = compiled.as_text()
    assert len(re.findall(r"custom-call\([^\n]*sum_copies", text)) == 2 * 12
    assert not re.search(rf"\[[\d,]*{seq},{seq}\]", text)
    # the attention layer's core: target, online, recomputed; one backward
    assert _core_kernel_calls(text, "causal_attention") == [3, 1]
    assert "f32[2,8,4,512,512]" not in text   # no score tile crosses HBM
    assert "f32[8,8,4,512,512]" not in text
    assert not _float32_squares(text)
    for scope in ("shortconv/proj", "shortconv/core", "gqa/core", "/ffn/",
                  "moe/experts/combine", "moe/route/choose",
                  "moe/route/tables"):
        assert scope in text, scope
    # the four routing layers without a sort (PR 46): the choices and the
    # tables a kernel each in target, online and recomputed forward, the
    # backward of ``weight_of`` one more a layer
    for kernel, calls in (("route_choose", 12), ("route_tables", 12),
                          ("route_tables_bwd", 4)):
        assert len(re.findall(
            rf"custom-call\([^\n]*/{kernel}/pallas_call", text)) == calls
    assert not [row for row in _route_rows(text) if row[0] == "sort"]
