"""The one-stream latent-attention decoder trunk (latent attention with a
plain residual and plain rotary embedding on a blockwise causal core whose
keys are wider than its values and share one rotary part, a leading dense
layer, a sigmoid router with the ``noaux_tc`` bias over experts of which
this chip holds a share, one shared expert) against the plain reference, on
the CPU in float32 at the tiny preset: hidden 32, 3 layers (1 dense + 2), 4
heads of 16 + 8 wide keys and 8-wide values, 16 experts top-4, blocks of 8
keys (three a row at 20 tokens, the last short).

Tolerances as tests/test_shortconv_trunk.py: two float32 implementations of
the same equations that differ in the ORDER of sums (softmax over blocks of
keys with a running max against the whole row; two products a tile against
one; sorted ragged products against a loop over experts): 1e-5 relative on
values, 1e-3 on a leaf's gradient.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_latent_trunk as reference
from benchmarks.lib import weights_shortconv_trunk
from byol_tpu.core import config as config_lib
from byol_tpu.models import decoder_trunk as trunk_lib
from byol_tpu.ops import attention
from byol_tpu.ops import causal_attention as kernels
from byol_tpu.parallel.compile_plan import build_plan
from byol_tpu.parallel.mesh import MeshSpec, build_mesh, shard_batch_to_mesh

TINY = trunk_lib.LATENT_TINY
SEQ, BATCH, D = 20, 4, 32
SHARE = "1/4,vocab=2,heads=1"          # 4 of 16 experts, 64 of 128 rows
CONF = dict(                           # the tiny preset, as a file's keys
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=8, kv_lora_rank=16,
    num_experts_per_tok=4, routed_scaling_factor=2.5, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=32e6, rope_scaling=None, hc_mult=1,
    n_group=1, topk_group=1, n_routed_experts=4,
    published={"n_routed_experts": 16}, layer_share=SHARE)
# the weights' bias (0.01 N(0, 1)) is sized for 256 experts at 32,768 tokens;
# among 16 experts and a few dozen tokens it has to be larger to move a choice
BIAS_SCALE = 20.0


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _normal(seed, *shape, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32).astype(dtype)


def _sizes(share=SHARE):
    held = 16 // int(share.split(",")[0].split("/")[1])
    return reference.sizes_of(dict(CONF, layer_share=share,
                                   n_routed_experts=held))


def _seeded(like, seed=5):
    # the weights' rules read a leaf's place in the WHOLE tree
    tree = weights_shortconv_trunk.make_weights(
        {"backbone": like}, {}, seed)[0]["backbone"]
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * BIAS_SCALE if "e_score_correction_bias" in
        jax.tree_util.keystr(path) else x, tree)


def _module_and_weights(module, name, seed, batch=2, seq=SEQ):
    x = _normal(seed, batch, seq, D)
    like = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), x))
    params = _seeded({"layer1": {name: like["params"]}}, seed)["layer1"][name]
    return params, x


def _leafwise_close(got, want, rtol=1e-3):
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_leaves(want)
    largest = max(float(jnp.linalg.norm(w)) for w in flat_want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        gap = float(jnp.linalg.norm(g - w))
        assert gap <= rtol * float(jnp.linalg.norm(w)) + 1e-6 * largest, \
            (jax.tree_util.keystr(path), gap, float(jnp.linalg.norm(w)))
    return len(flat_got)


# ---- the core: keys wider than values, one part of the key shared ----------

def _core_inputs(seed, *, batch, heads, kv_heads, seq, dim, vdim, rope,
                 dtype=jnp.float32):
    f = functools.partial(_normal, dtype=dtype)
    q, k, v = (f(seed, batch, heads, seq, dim),
               f(seed + 1, batch, kv_heads, seq, dim),
               f(seed + 2, batch, kv_heads, seq, vdim))
    shared = (f(seed + 3, batch, heads, seq, rope),
              f(seed + 4, batch, seq, rope)) if rope else None
    return q, k, v, shared


def _whole_rows(q, k, v, shared):
    """``dense_attention`` over whole rows, the shared key copied a head."""
    b, hq, s, _ = q.shape
    repeat = lambda x: jnp.repeat(x, hq // k.shape[1], axis=1)
    k, v = repeat(k), repeat(v)
    if shared is not None:
        q = jnp.concatenate([q, shared[0]], -1)
        k = jnp.concatenate([k, jnp.broadcast_to(
            shared[1][:, None], (b, hq, s, shared[1].shape[-1]))], -1)
    return attention.dense_attention(q, k, v, scale=q.shape[-1] ** -0.5,
                                     causal=True)


def _value_and_grads(core, q, k, v, shared):
    def loss(*args):
        out = core(*args)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3) if shared is not None else (0, 1, 2),
        has_aux=True))(q, k, v, shared)
    return [out] + jax.tree_util.tree_leaves(grads)


@pytest.mark.parametrize("heads,kv_heads,rope", [
    (4, 4, 0),      # a value width of its own (ROADMAP item 12's "allows")
    (4, 2, 0),      # ... under grouped key heads
    (4, 4, 8),      # latent attention: one rotary key for all heads
])
def test_the_jnp_core_takes_values_narrower_than_keys(heads, kv_heads, rope):
    """Forward and every gradient against ``dense_attention`` over whole
    rows at 16 (+ 8) wide keys and 8-wide values, three blocks of 8 keys a
    row, the last short; ``d_k_s`` is the sum over the heads."""
    args = _core_inputs(heads + rope, batch=2, heads=heads,
                        kv_heads=kv_heads, seq=SEQ, dim=16, vdim=8,
                        rope=rope)
    blockwise = lambda q, k, v, shared: \
        attention.blockwise_causal_attention(q, k, v, block=8, shared=shared)
    got = _value_and_grads(blockwise, *args)
    want = _value_and_grads(_whole_rows, *args)
    assert got[0].shape == (2, heads, SEQ, 8)
    assert len(got) == (6 if rope else 4)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dim,rope,dtype", [
    (128, 64, "float32"),      # (b): two products a tile, the key shared
    (128, 64, "bfloat16"),
    (256, 0, "float32"),       # (a): one operand of two lane tiles
])
def test_the_kernel_pair_is_the_jnp_body_at_unequal_widths(
        monkeypatch, dim, rope, dtype):
    """Under the Pallas interpreter at 2 sequences x 2 heads, 256 tokens in
    blocks of 128, 128-wide values: values and every gradient, the shared
    key's summed over the heads inside the kernel."""
    args = _core_inputs(dim, batch=2, heads=2, kv_heads=2, seq=256, dim=dim,
                        vdim=128, rope=rope, dtype=jnp.dtype(dtype))
    core = lambda q, k, v, shared: attention.blockwise_causal_attention(
        q, k, v, block=128, shared=shared)
    both, calls, attend = [], [], kernels.attend
    monkeypatch.setattr(kernels, "attend", lambda *a, **kw: calls.append(
        kw["shared"] is not None) or attend(*a, **kw))
    for taken in (False, True):
        monkeypatch.setattr(kernels, "applies", lambda *a, **kw: taken)
        both.append(_value_and_grads(core, *args))
    want, got = both
    assert calls == [bool(rope)]          # the second arm ran the kernels
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    for name, g, w in zip("out d_q d_k d_v d_q_s d_k_s".split(), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        # (the kernel adds the heads' ``d_k_s`` in float32 and rounds once,
        # the body rounds a head's and adds in the input dtype: 3.0e-3
        # apart in bfloat16, the kernel the nearer to float32's)
        limit = {"float32": 1e-5, "bfloat16": 1e-3}[dtype] * (
            5 if name == "d_k_s" else 1)
        assert np.linalg.norm(f32(g) - f32(w)) <= limit * np.linalg.norm(
            f32(w)), name
    assert got[0].shape == (2, 2, 256, 128)


@pytest.mark.parametrize("dim,vdim,shared,backend,taken", [
    (128, 128, 64, "tpu", True),     # latent attention's: 128 + 64 | 128
    (256, 128, 0, "tpu", True),      # ... padded to two lane tiles
    (192, 128, 0, "tpu", False),     # 1.5 lane tiles: the jax.numpy body
    (128, 96, 64, "tpu", False),     # a value of 3/4 of a lane tile
    (128, 128, 32, "tpu", False),    # a shared part of a quarter
    (128, 128, 64, "cpu", False),    # not lowered for a TPU
])
def test_the_kernels_learn_a_key_width_and_a_value_width(
        dim, vdim, shared, backend, taken):
    assert kernels.applies(512, dim, 4096, 32, 32, jnp.bfloat16, vdim=vdim,
                           shared=shared, backend=backend) is taken
    # what a selection adds to the count is the mask's int8 block, twice
    for fwd in (True, False):
        assert kernels._vmem_bytes(512, 128, 4096, 8, 2, fwd) == \
            kernels._vmem_bytes(512, 128, 4096, 8, 2, fwd, selected=True) \
            - 2 * 512 * 512
    # all 32 heads at 4,096 keys, one head a program: well inside 48 MiB
    assert kernels._vmem_bytes(512, 128, 4096, 1, 2, False, vdim=128,
                               shared=64) < 24 * 2 ** 20


# ---- the layer --------------------------------------------------------------

@pytest.mark.parametrize("seq,blockwise", [(SEQ, True), (16, False)])
def test_latent_attention_matches_the_reference_on_both_cores(
        monkeypatch, seq, blockwise):
    """Past two blocks of keys the layer takes the blockwise core (the
    rotary key handed over once), up to two the dense one: both are the
    reference's whole-row softmax, forward and back."""
    layer = trunk_lib.LatentAttention(TINY, 4)
    params, x = _module_and_weights(layer, "attn", 7, seq=seq)
    called = []
    core = trunk_lib.blockwise_causal_attention
    monkeypatch.setattr(
        trunk_lib, "blockwise_causal_attention",
        lambda *a, **kw: called.append(kw["shared"][1].shape) or core(
            *a, **kw))
    ct = _normal(2, *x.shape)
    program = lambda p, x: jnp.sum(layer.apply({"params": p}, x) * ct)
    plain = lambda p, x: sum(jnp.sum(reference.latent_attention(
        p, row, _sizes(), "float32") * c) for row, c in zip(x, ct))
    got = jax.jit(jax.value_and_grad(program, argnums=(0, 1)))(params, x)
    want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(params, x)
    assert called == ([(2, seq, 8)] if blockwise else [])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    # q_a, q_norm, q_b, kv_a, kv_norm, kv_b, o and the input
    assert _leafwise_close(got[1], want[1], rtol=1e-4) == 8


def test_an_output_does_not_move_when_a_later_input_does():
    layer = trunk_lib.LatentAttention(TINY, 4)
    params, x = _module_and_weights(layer, "attn", 6, batch=1)
    t = 11
    moved = x.at[:, t + 1:].add(1.0)
    out, out_moved = (layer.apply({"params": params}, v) for v in (x, moved))
    np.testing.assert_array_equal(out[:, :t + 1], out_moved[:, :t + 1])
    assert float(jnp.abs(out[:, t + 1:] - out_moved[:, t + 1:]).min()) > 0.0


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """The four shares' routed parts, the shared expert ONCE and attention
    (whole on every chip) ONCE give the uncut reference's layer."""
    z = TINY
    whole = trunk_lib.ExpertLayer(z, 0, z.n_routed_experts)
    p_moe, h = _module_and_weights(whole, "moe", 4)
    p_attn, x = _module_and_weights(trunk_lib.LatentAttention(z, 4), "attn",
                                    3)
    assert set(p_moe) == {"router", "e_score_correction_bias", "experts",
                          "shared"}
    uncut = _sizes("0/1")
    gain = jnp.ones((D,))
    layer = {"attn": p_attn, "moe": p_moe, "attn_norm": {"scale": gain},
             "ffn_norm": {"scale": gain}}
    want = jnp.stack([reference.trunk_layer(layer, r, uncut, "float32")[0]
                      for r in x])
    norm = lambda v: v * jax.lax.rsqrt(
        jnp.mean(v * v, -1, keepdims=True) + z.rms_norm_eps)
    share = trunk_lib.LayerShare.parse(SHARE)
    assert share.held(z.num_attention_heads, "attention heads") == (0, 4)
    after = x + trunk_lib.LatentAttention(z, 4).apply({"params": p_attn},
                                                      norm(x))
    h = norm(after)
    shared = trunk_lib.GatedMLP(z.moe_intermediate_size).apply(
        {"params": p_moe["shared"]}, h)
    routed = 0.0
    for index in range(4):
        lo, held = trunk_lib.LayerShare.parse(
            f"{index}/4,vocab=2,heads=1").held(z.n_routed_experts, "experts")
        assert (lo, held) == (4 * index, 4)
        part = dict(p_moe, experts={k: v[lo:lo + held]
                                    for k, v in p_moe["experts"].items()})
        one = jax.jit(lambda p, lo=lo: trunk_lib.ExpertLayer(z, lo, 4).apply(
            {"params": p}, h))(part)
        # ... and a share is what the reference gives for that share
        np.testing.assert_allclose(one, jnp.stack([reference.expert_layer(
            part, r, _sizes(f"{index}/4"), "float32")[0] for r in h]),
            rtol=1e-4, atol=1e-5)
        routed += one - shared
    np.testing.assert_allclose(after + routed + shared, want, rtol=1e-4,
                               atol=1e-5)


# ---- the sizes -------------------------------------------------------------

def test_the_published_sizes_build_the_parameters_the_config_implies():
    """``jax.eval_shape`` alone: the parameter count by part is the
    configuration file's memory note (and ISSUE 40's arithmetic)."""
    import json
    import os
    from byol_tpu.models.registry import get_backbone, held_vocab_rows
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "byol_joyai_llm_flash_ep16.json")) as f:
        conf = json.load(f)
    share, depth = conf["layer_share"], conf["trunk_depth"]
    module, dim = get_backbone("joyai_llm_flash", layer_share=share,
                               trunk_depth=depth)
    assert dim == 2048 and held_vocab_rows("joyai_llm_flash", share) == 16160
    assert module.trace_scopes == trunk_lib.TRACE_SCOPES
    assert "mla/core" in trunk_lib.TRACE_SCOPES
    z = module.sizes
    assert (z.hc_mult, z.rope_factor, z.qk_head_dim, z.attention_block) == (
        1, 1.0, 192, 512)
    like = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree_util.tree_leaves(tree))
    layers = sum(int(n) for n in depth.split("+"))
    assert sorted(like) == ["embed", "final_norm"] + [
        f"layer{i}" for i in range(layers)]
    # one stream: no hyper-connection anywhere
    assert set(like["layer1"]) == {"attn", "attn_norm", "ffn_norm", "moe"}
    attn = like["layer0"]["attn"]
    by_part = {k: count(attn[k]) for k in ("q_a", "q_b", "kv_a", "kv_b", "o")}
    assert by_part == {"q_a": 2048 * 1536, "q_b": 1536 * 32 * 192,
                       "kv_a": 2048 * 576, "kv_b": 512 * 32 * 256,
                       "o": 32 * 128 * 2048}
    assert count(attn) == 26_345_472 + 1536 + 512          # two gains
    assert count(like["layer0"]["ffn"]) == 3 * 2048 * 7168  # 44.04 M
    moe = like["layer1"]["moe"]
    assert moe["router"].shape == (2048, 256)
    assert moe["e_score_correction_bias"].shape == (256,)
    assert count(moe["experts"]) == 16 * 3 * 2048 * 768     # 75.50 M
    assert count(moe["shared"]) == 3 * 2048 * 768           # 4.72 M
    # an uncut expert layer: 256 x 4.72 M = 1,208 M
    assert 256 * count(moe["experts"]) // 16 == 1_207_959_552
    assert count(like["embed"]) == 16160 * 2048             # 33.10 M
    heads = 2048 * 4096 + 4096 * 256 + 256 * 4096 + 4096 * 256 + 2048 * 1000
    total = count(like) + heads
    memory = conf["notes"]["memory"]
    assert f"{total / 1e6:.1f} M" in memory, (total, memory)
    for part in (count(attn), count(like["layer1"]), count(like["layer0"])):
        assert f"{part / 1e6:.2f}" in memory, (part, memory)


# ---- the trunk and the step ------------------------------------------------

@pytest.fixture(scope="module")
def trunk_and_weights():
    tokens = jnp.asarray(np.random.default_rng(2).integers(
        0, 64, (2, SEQ)), jnp.int32)
    trunk = trunk_lib.DecoderTrunk(TINY, trunk_lib.LayerShare.parse(SHARE),
                                   remat=True, remat_policy="full")
    like = jax.eval_shape(lambda: trunk.init(
        jax.random.PRNGKey(0), tokens))["params"]
    return trunk, _seeded(like), tokens


def test_the_trunks_features_and_gradients_match_the_reference(
        trunk_and_weights):
    trunk, params, tokens = trunk_and_weights
    ct = _normal(3, 2, D)

    def program(p):
        feats, _ = trunk.apply({"params": p}, tokens,
                               mutable=[trunk_lib.ROUTING])
        return jnp.sum(feats * ct), feats

    def plain(p):
        feats = jnp.stack([reference.trunk(p, t, _sizes()) for t in tokens])
        return jnp.sum(feats * ct), feats
    (_, feats), got = jax.jit(jax.value_and_grad(program, has_aux=True))(
        params)
    (_, want_feats), want = jax.jit(jax.value_and_grad(plain, has_aux=True))(
        params)
    np.testing.assert_allclose(feats, want_feats, rtol=1e-5, atol=1e-6)
    # EVERY leaf: 7 of attention and 2 gains a layer; 3 of the dense FFN;
    # router, bias, 3 + 3 of the experts; embedding, final norm
    assert _leafwise_close(got, want) == 3 * 9 + 3 + 2 * 8 + 2
    for tree in (got, want):       # the bias's gradient is zero on both sides
        assert float(jnp.abs(tree["layer1"]["moe"][
            "e_score_correction_bias"]).max()) == 0.0
    # bfloat16 in float32's place fails the features' tolerance
    low = jnp.stack([reference.trunk(params, t, _sizes(), "bfloat16")
                     for t in tokens])
    assert float(jnp.linalg.norm(low - want_feats)
                 / jnp.linalg.norm(want_feats)) > 4e-3


@pytest.fixture(scope="module")
def training():
    """The normal path: Config -> resolve -> mesh -> plan ->
    setup_training, at the tiny preset, with the seeded weights."""
    from byol_tpu.training.build import setup_training
    c = config_lib.Config()
    c = c.replace(
        task=dataclasses.replace(c.task, task="synth_tokens",
                                 batch_size=BATCH, epochs=4, seq_len=SEQ),
        model=dataclasses.replace(
            c.model, arch="latent_trunk_tiny", head_latent_size=32,
            projection_size=16, fuse_views=True, remat_policy="full",
            layer_share=SHARE, trunk_depth="1+2"),
        optim=dataclasses.replace(c.optim, warmup=1),
        device=dataclasses.replace(c.device, num_replicas=1, half=False,
                                   telemetry="step"))
    rcfg = config_lib.resolve(c, num_train_samples=4 * BATCH,
                              num_test_samples=BATCH, output_size=10,
                              input_shape=(SEQ,))
    mesh = build_mesh(MeshSpec(data=1), jax.devices()[:1])
    with jax.default_matmul_precision("highest"):
        _, state, step, _, _ = setup_training(
            rcfg, mesh, jax.random.PRNGKey(0), plan=build_plan(mesh))
        like = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (state.params, state.batch_stats))
        params, target, stats = weights_shortconv_trunk.make_weights(
            *like, 11, copies=2)
    return mesh, state.replace(params=params, target_params=target,
                               batch_stats=stats), step


def _batches(n, seed=3):
    rng = np.random.default_rng(seed)
    return [{"view1": rng.integers(0, 64, (BATCH, SEQ), dtype=np.int32),
             "view2": rng.integers(0, 64, (BATCH, SEQ), dtype=np.int32),
             "label": rng.integers(0, 10, (BATCH,)).astype(np.int32)}
            for _ in range(n)]


def test_three_optimizer_steps_match_the_reference(training):
    """Loss, the momentum after one step (the gradients) and the parameters
    after three, through ``setup_training``'s own step; the step stamps
    ``mla/core`` and its ops carry the scope."""
    from byol_tpu.optim.factory import extract_sgdm_state
    mesh, state, step = training
    batches = _batches(3)
    with mesh:
        text = step.__wrapped__.lower(state, shard_batch_to_mesh(
            dict(batches[0]), mesh)).as_text(debug_info=True)
    stamped = text.split('phase_scopes = "')[1].split('"')[0].split()
    assert set(trunk_lib.TRACE_SCOPES) <= set(stamped)
    assert "/mla/attn/core/" in text and "mhc/" not in text
    # ``train_step`` donates its state: the fixture's stays whole
    state = jax.tree_util.tree_map(jnp.array, state)
    params0 = jax.device_get(state.params)
    losses, first = [], None
    for i, b in enumerate(batches):
        state, metrics = step(state, shard_batch_to_mesh(dict(b), mesh))
        losses.append(float(metrics["loss_mean"]))
        if i == 0:
            first = jax.device_get(extract_sgdm_state(state.opt_state)[0])
            assert float(metrics["_moe_rows_dropped"]) == 0.0
            assert float(metrics["_moe_rows_held"]) > 0.0
    hp = {"lr": 0.2, "weight_decay": 1e-6, "base_decay": 0.996,
          "global_batch": BATCH, "warmup_steps": 4, "total_steps": 16}
    want = reference.train_steps(params0, batches, hp, conf=CONF)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    for name, got_tree, want_tree, rtol in (
            ("momentum", first, want["first_trace"], 1e-3),
            ("parameters", jax.device_get(state.params), want["params"],
             2e-5)):
        start = jax.tree_util.tree_leaves(params0)
        largest = max(float(np.linalg.norm(w)) for w in
                      jax.tree_util.tree_leaves(want["first_trace"]))
        for (path, g), w, p0 in zip(
                jax.tree_util.tree_flatten_with_path(got_tree)[0],
                jax.tree_util.tree_leaves(want_tree), start):
            w = np.asarray(w)
            ref_size = np.linalg.norm(w - p0 if name == "parameters" else w)
            # (a bias before a BatchNorm has no gradient but rounding)
            assert np.linalg.norm(g - w) <= rtol * ref_size \
                + 1e-6 * largest, (name, jax.tree_util.keystr(path))
