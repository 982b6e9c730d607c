"""The short-convolution decoder trunk (gated short convolutions beside plain
grouped-query attention in a listed layer pattern, a leading dense layer, a
sigmoid router with a selection bias over experts of which this chip holds a
share, NO shared expert) against the plain reference, on the CPU in float32
at the tiny preset: hidden 32, 7 published layers cut ``1+4`` to published
layers 0, 2, 3, 4, 5 (conv | attention, conv, conv, conv), 4 query on 2
key/value heads of 8, 3 taps, 8 experts top-2, blocks of 8 keys (three a row
at 20 tokens, the last short).

Tolerances as tests/test_sparse_trunk.py: program and reference are two
float32 implementations of the same equations that differ in the ORDER of
sums (softmax over blocks of keys with a running max against the whole row;
``top_k`` against a full sort; sorted ragged products against a loop over
experts; fused views against one sequence at a time): 1e-5 relative on
values, 1e-3 on a leaf's gradient (sums of thousands of float32 terms).
bfloat16 in float32's place reads 4e-3 or more on every one
(``test_bfloat16_in_float32s_place_fails``).  A CHOICE is all or nothing:
two biased scores closer than their rounding would flip an expert between
the two — none is at these seeds.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_shortconv_trunk as reference
from benchmarks.lib import weights_shortconv_trunk
from byol_tpu.core import config as config_lib
from byol_tpu.models import decoder_trunk as trunk_lib
from byol_tpu.optim import lars as lars_lib
from byol_tpu.parallel.compile_plan import build_plan
from byol_tpu.parallel.mesh import MeshSpec, build_mesh, shard_batch_to_mesh

PUBLISHED = trunk_lib.SHORTCONV_TINY
TINY = PUBLISHED.with_depth(1, 4)
SEQ, BATCH, D = 20, 4, 32
SHARE = "1/4,vocab=2,heads=1"                  # 2 of 8 experts, 64 of 128 rows
DEPTH = "1+4"
CONF = dict(                                   # the tiny preset, as a
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,  # file's
    conv_L_cache=3, conv_bias=False, norm_eps=1e-5,                # keys
    rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
    num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=1,
    use_expert_bias=True, num_experts=2, published={"num_experts": 8},
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    layer_share=SHARE)
# the weights' bias (0.01 N(0, 1)) is sized for 64 experts at 32,768 tokens;
# among 8 experts and a few dozen tokens it has to be larger to move a choice
BIAS_SCALE = 20.0


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(seed, batch=BATCH, vocab=64):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, vocab, (batch, SEQ)), jnp.int32)


def _trunk(share=SHARE, **kw):
    return trunk_lib.DecoderTrunk(TINY, trunk_lib.LayerShare.parse(share),
                                  **kw)


def _larger_bias(tree):
    """The same tree with every selection bias ``BIAS_SCALE`` times as
    large."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * BIAS_SCALE if "e_score_correction_bias" in
        jax.tree_util.keystr(path) else x, tree)


def _seeded(like, seed=5):
    # the weights' rules read a leaf's place in the WHOLE tree
    return _larger_bias(weights_shortconv_trunk.make_weights(
        {"backbone": like}, {}, seed)[0]["backbone"])


def _sizes(share=SHARE):
    held = 8 // int(share.split(",")[0].split("/")[1])
    return reference.sizes_of(dict(CONF, layer_share=share,
                                   num_experts=held))


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


def _module_and_weights(module, name, seed, batch=3):
    """``module`` with seeded weights as layer 0's ``name``, and an input."""
    x = jnp.asarray(np.random.default_rng(seed).normal(
        size=(batch, SEQ, D)), jnp.float32)
    like = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), x))
    params = _seeded({"layer0": {name: like["params"]}}, seed)[
        "layer0"][name]
    return params, x


# ---- the sizes -------------------------------------------------------------

def test_a_cut_in_depth_keeps_each_kept_layers_published_mixer():
    conv, gqa = "shortconv", "gqa"
    assert PUBLISHED.layer_mixers == (conv, conv, gqa, conv, conv, conv, gqa)
    assert TINY.layer_mixers == (conv, gqa, conv, conv, conv)
    assert (TINY.num_hidden_layers, TINY.first_k_dense_replace) == (5, 1)
    assert [TINY.mixer(i) for i in range(5)] == list(TINY.layer_mixers)
    big = trunk_lib.LFM2_24B_A2B
    assert [i for i in range(40) if big.mixer(i) == gqa] == list(
        range(2, 40, 4))
    assert big.with_depth(1, 4).layer_mixers == tuple(
        big.layer_mixers[i] for i in (0, 2, 3, 4, 5)) == (
            conv, gqa, conv, conv, conv)
    assert big.with_depth(2, 6).layer_mixers == big.layer_mixers[:8]
    # a trunk whose pattern is a rule keeps it
    hybrid = trunk_lib.QWEN3_NEXT_80B_A3B.with_depth(0, 4)
    assert hybrid.layer_mixers == () and hybrid.mixer(3) == gqa


def test_the_published_sizes_build_the_parameters_the_config_implies():
    from byol_tpu.models.registry import get_backbone, held_vocab_rows
    share = "0/8,vocab=8,heads=1"
    module, dim = get_backbone("lfm2_24b_a2b", layer_share=share,
                               trunk_depth=DEPTH)
    assert dim == 2048 and held_vocab_rows("lfm2_24b_a2b", share) == 8192
    assert module.trace_scopes == trunk_lib.SHORTCONV_SCOPES
    like = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree_util.tree_leaves(tree))
    assert sorted(like) == ["embed", "final_norm"] + [
        f"layer{i}" for i in range(5)]
    assert [next(k for k in ("shortconv", "gqa") if k in like[f"layer{i}"])
            for i in range(5)] == list(module.sizes.layer_mixers)
    # a conv mixer 16.78 M: W_in 12.58, W_out 4.19, 6,144 taps
    assert count(like["layer0"]["shortconv"]) == 4 * 2048 ** 2 + 3 * 2048
    assert count(like["layer0"]["ffn"]) == 3 * 2048 * 11776        # 72.35 M
    # attention 10.49 M: q and o 4.19 each, k and v 1.05 each, two gains
    assert count(like["layer1"]["gqa"]) == (
        2 * 2048 ** 2 + 2 * 2048 * 512 + 2 * 64)
    moe = like["layer1"]["moe"]
    assert set(moe) == {"router", "e_score_correction_bias", "experts"}
    assert moe["router"].shape == (2048, 64)
    assert count(moe["experts"]) == 8 * 3 * 2048 * 1536             # 75.50 M
    # an uncut expert layer: 64 x 9.437 M = 604.0 M
    assert 64 * count(moe["experts"]) // 8 == 603_979_776
    assert count(like["embed"]) == 8192 * 2048
    # 469.3 M, and 13.6 M of heads and probe: 482.9 M
    heads = 2048 * 4096 + 4096 * 256 + 256 * 4096 + 4096 * 256 + 2048 * 1000
    assert 482.4e6 < count(like) + heads < 483.4e6


def test_lars_adapts_the_taps_as_one_kernel_and_an_expert_alone():
    like = jax.eval_shape(lambda: _trunk().init(
        jax.random.PRNGKey(0), _tokens(9)))["params"]
    mask = lars_lib.default_exclusion_mask(like)
    conv = mask["layer0"]["shortconv"]
    assert conv["conv"] is True and like["layer0"]["shortconv"][
        "conv"].shape == (3, D)
    assert conv["in_proj"]["kernel"] is True
    assert conv["out_proj"]["kernel"] is True
    gqa, moe = mask["layer1"]["gqa"], mask["layer1"]["moe"]
    assert all(gqa[name]["kernel"] is True for name in "qkvo")
    assert gqa["q_norm"]["scale"] is False and gqa["k_norm"]["scale"] is False
    assert moe["e_score_correction_bias"] is False and moe["router"] is True
    assert moe["experts"]["gate"] == lars_lib.PER_EXPERT


# ---- the mixers, alone -----------------------------------------------------

def test_the_short_convolution_matches_the_reference_forward_and_back():
    layer = trunk_lib.ShortConv(3)
    params, x = _module_and_weights(layer, "shortconv", 5)
    assert set(params) == {"in_proj", "conv", "out_proj"}
    ct = jnp.asarray(np.random.default_rng(1).normal(size=x.shape),
                     jnp.float32)
    program = lambda p, x: jnp.sum(layer.apply({"params": p}, x) * ct)
    plain = lambda p, x: sum(jnp.sum(reference.short_conv(
        p, row, _sizes(), "float32") * c) for row, c in zip(x, ct))
    got = jax.jit(jax.value_and_grad(program, argnums=(0, 1)))(params, x)
    want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(params, x)
    assert _leafwise_close(got[1], want[1], rtol=1e-4) == 4
    np.testing.assert_allclose(
        layer.apply({"params": params}, x),
        jnp.stack([reference.short_conv(params, row, _sizes(), "float32")
                   for row in x]), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mixer", ["shortconv", "gqa"])
def test_an_output_does_not_move_when_a_later_input_does(mixer):
    if mixer == "shortconv":
        layer = trunk_lib.ShortConv(3)
    else:
        layer = trunk_lib.GatedAttention(TINY.gated_attention, 4, 2, 1e-5,
                                         zero_centred=False)
    params, x = _module_and_weights(layer, mixer, 6, batch=1)
    t = 11
    moved = x.at[:, t + 1:].add(1.0)
    out, out_moved = (layer.apply({"params": params}, v) for v in (x, moved))
    np.testing.assert_array_equal(out[:, :t + 1], out_moved[:, :t + 1])
    assert float(jnp.abs(out[:, t + 1:] - out_moved[:, t + 1:]).min()) > 0.0
    if mixer == "shortconv":
        # ... and three taps reach two tokens back, no further
        back = jax.jacobian(lambda v: layer.apply(
            {"params": params}, v)[0, t].sum())(x)[0]
        reach = np.flatnonzero(np.abs(np.asarray(back)).sum(axis=-1))
        assert reach.tolist() == [t - 2, t - 1, t]


def test_the_attention_layer_matches_the_reference_forward_and_back():
    layer = trunk_lib.GatedAttention(TINY.gated_attention, 4, 2, 1e-5,
                                     zero_centred=False)
    params, x = _module_and_weights(layer, "gqa", 7)
    # plain: no gate beside the query, gains from ones
    assert params["q"]["kernel"].shape == (D, 4 * 8)
    assert params["k"]["kernel"].shape == (D, 2 * 8)
    ct = jnp.asarray(np.random.default_rng(2).normal(size=x.shape),
                     jnp.float32)
    program = lambda p, x: jnp.sum(layer.apply({"params": p}, x) * ct)
    plain = lambda p, x: sum(jnp.sum(reference.attention(
        p, row, _sizes(), "float32") * c) for row, c in zip(x, ct))
    got = jax.jit(jax.value_and_grad(program, argnums=(0, 1)))(params, x)
    want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(params, x)
    assert _leafwise_close(got[1], want[1], rtol=1e-4) == 7
    np.testing.assert_allclose(
        layer.apply({"params": params}, x),
        jnp.stack([reference.attention(params, row, _sizes(), "float32")
                   for row in x]), rtol=1e-5, atol=1e-6)


# ---- the expert layer ------------------------------------------------------

def _expert_layer(seed=4):
    whole = trunk_lib.ExpertLayer(TINY, 0, TINY.n_routed_experts)
    p_moe, x = _module_and_weights(whole, "moe", seed, batch=2)
    assert set(p_moe) == {"router", "e_score_correction_bias", "experts"}
    return p_moe, x


def test_the_bias_moves_the_choice_and_never_the_weights():
    p_moe, x = _expert_layer()
    z = _sizes("0/1")
    rows = x.reshape(-1, D)
    chosen, weight = reference.routing(p_moe, rows, z, "float32")
    unbiased, _ = reference.routing(
        dict(p_moe, e_score_correction_bias=jnp.zeros(8)), rows, z,
        "float32")
    moved = np.flatnonzero((np.sort(chosen, -1)
                            != np.sort(unbiased, -1)).any(-1))
    assert 3 <= len(moved) < len(rows)           # some choices, not all
    # the weights are the chosen experts' UNBIASED scores over their sum + 1e-6
    scores = jax.nn.sigmoid(rows @ p_moe["router"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        weight, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert float(jnp.abs(weight.sum(-1) - 1.0).max()) < 2e-6
    assert float((1.0 - weight.sum(-1)).min()) > 2e-7     # the 1e-6 is there
    # the program chooses and weighs the same: with ONE expert's kernels
    # non-zero, the layer's output is that expert's part alone
    layer = jax.jit(lambda p: trunk_lib.ExpertLayer(TINY, 0, 8).apply(
        {"params": p}, x))
    for expert in (0, 5):
        only = jax.tree_util.tree_map(
            lambda w: w.at[:expert].set(0).at[expert + 1:].set(0),
            p_moe["experts"])
        got = layer(dict(p_moe, experts=only)).reshape(-1, D)
        sent = (chosen == expert).any(-1)
        assert 0 < int(sent.sum()) < len(rows)
        assert float(jnp.abs(got[~sent]).max()) == 0.0
        assert float(jnp.abs(got[sent]).min(axis=0).max()) > 0.0
    # the bias takes no gradient
    grad = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(
        trunk_lib.ExpertLayer(TINY, 0, 8).apply({"params": p}, x)))))(p_moe)
    assert float(jnp.abs(grad["e_score_correction_bias"]).max()) == 0.0
    assert float(jnp.abs(grad["router"]).max()) > 0.0


@pytest.mark.parametrize("of", [4, 8])
def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(of):
    """``of`` shares of the 8 experts (2 each, or 1 each as the cell's 8 of
    64): the routed parts summed give the uncut expert layer — nothing every
    chip computes alike rides along, the layer has no shared expert."""
    p_moe, x = _expert_layer()
    want = jnp.stack([reference.expert_layer(p_moe, r, _sizes("0/1"),
                                             "float32")[0] for r in x])
    routed = 0.0
    for index in range(of):
        share = trunk_lib.LayerShare.parse(f"{index}/{of},vocab=2,heads=1")
        lo, held = share.held(TINY.n_routed_experts, "routed experts")
        assert (lo, held) == (8 // of * index, 8 // of)
        part = dict(p_moe, experts={k: v[lo:lo + held]
                                    for k, v in p_moe["experts"].items()})
        one = jax.jit(lambda p, lo=lo, held=held: trunk_lib.ExpertLayer(
            TINY, lo, held).apply({"params": p}, x))(part)
        # ... and a share is what the reference gives for that share
        np.testing.assert_allclose(one, jnp.stack([reference.expert_layer(
            part, r, _sizes(f"{index}/{of}"), "float32")[0] for r in x]),
            rtol=1e-4, atol=1e-5)
        routed += one
    np.testing.assert_allclose(routed, want, rtol=1e-4, atol=1e-5)
    # what every chip computes alike are the mixers, whole
    assert share.held(TINY.gated_attention.num_heads,
                      "attention heads") == (0, 4)


# ---- the trunk and the step ------------------------------------------------

def _reference_trunk(params, tokens, precision="float32"):
    return jnp.stack([reference.trunk(params, t, _sizes(), precision)
                      for t in tokens])


@pytest.fixture(scope="module")
def trunk_and_weights():
    tokens = _tokens(2, batch=2)
    trunk = _trunk(remat=True, remat_policy="full")
    like = jax.eval_shape(lambda: trunk.init(
        jax.random.PRNGKey(0), tokens))["params"]
    return trunk, _seeded(like), tokens


def test_the_trunks_features_and_gradients_match_the_reference(
        trunk_and_weights):
    trunk, params, tokens = trunk_and_weights
    ct = jnp.asarray(np.random.default_rng(3).normal(size=(2, D)),
                     jnp.float32)

    def program(p):
        feats, _ = trunk.apply({"params": p}, tokens,
                               mutable=[trunk_lib.ROUTING])
        return jnp.sum(feats * ct), feats

    def plain(p):
        feats = _reference_trunk(p, tokens)
        return jnp.sum(feats * ct), feats
    (_, feats), got = jax.jit(jax.value_and_grad(program, has_aux=True))(
        params)
    (_, want_feats), want = jax.jit(jax.value_and_grad(plain, has_aux=True))(
        params)
    np.testing.assert_allclose(feats, want_feats, rtol=1e-5, atol=1e-6)
    # EVERY leaf: 3 + 3 + 2 of the dense layer, 6 + 5 + 2 of the attention
    # layer, 3 + 5 + 2 of each of three convolution layers, embedding, norm
    assert _leafwise_close(got, want) == 8 + 13 + 3 * 10 + 2
    layer = params["layer1"]
    assert "shared" not in layer["moe"] and "ffn" in params["layer0"]
    # the bias's gradient is zero on both sides, the taps' is not
    for tree in (got, want):
        assert float(jnp.abs(tree["layer1"]["moe"][
            "e_score_correction_bias"]).max()) == 0.0
        assert float(jnp.abs(tree["layer2"]["shortconv"]["conv"]).min()) > 0.0


def test_bfloat16_in_float32s_place_fails(trunk_and_weights):
    _, params, tokens = trunk_and_weights
    want = _reference_trunk(params, tokens)
    low = _reference_trunk(params, tokens, "bfloat16")
    gap = float(jnp.linalg.norm(low - want) / jnp.linalg.norm(want))
    assert gap > 4e-3                       # the features' tolerance is 1e-5


def test_the_references_expert_loads_are_the_programs_routing(
        trunk_and_weights):
    """The rows each held expert of each routing layer is sent, by the
    reference's own count, against the program's counter of the rows it
    held."""
    trunk, params, tokens = trunk_and_weights
    rows = 0
    layer = jax.jit(lambda p, x: reference.trunk_layer(p, x, _sizes(),
                                                       "float32"))
    for sequence in tokens:
        x = params["embed"]["embedding"][sequence]
        for i in range(5):
            x, here = layer(params[f"layer{i}"], x)
            assert (here is None) == (i == 0)
            rows += 0 if here is None else int(here.sum())
    _, sown = jax.jit(lambda p: trunk.apply(
        {"params": p}, tokens, mutable=[trunk_lib.ROUTING]))(params)
    stats = sum(jax.tree_util.tree_leaves(sown[trunk_lib.ROUTING]))
    assert rows == int(stats[0]) > 0 and float(stats[3]) == 0.0


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
            c.model, arch="shortconv_trunk_tiny", head_latent_size=32,
            projection_size=16, fuse_views=True, remat_policy="full",
            layer_share=SHARE, trunk_depth=DEPTH),
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
        params, target, stats = _larger_bias(
            weights_shortconv_trunk.make_weights(*like, 11, copies=2))
    return mesh, state.replace(params=params, target_params=target,
                               batch_stats=stats), step


def _batches(n, seed=3):
    rng = np.random.default_rng(seed)
    return [{"view1": rng.integers(0, 64, (BATCH, SEQ), dtype=np.int32),
             "view2": rng.integers(0, 64, (BATCH, SEQ), dtype=np.int32),
             "label": rng.integers(0, 10, (BATCH,)).astype(np.int32)}
            for _ in range(n)]


def test_the_step_stamps_the_scopes_and_sums_the_routing_counters(training):
    mesh, state, step = training
    batch = shard_batch_to_mesh(dict(_batches(1)[0]), mesh)
    with mesh:
        text = step.__wrapped__.lower(state, batch).as_text(debug_info=True)
    stamped = text.split('phase_scopes = "')[1].split('"')[0].split()
    for scope in trunk_lib.SHORTCONV_SCOPES:
        assert scope in stamped
    assert not {"mla", "gdn", "dsa", "mhc"} & set(stamped)
    for scope in ("shortconv/proj", "shortconv/core", "gqa/core"):
        assert f"/{scope}/" in text


def test_three_optimizer_steps_match_the_reference(training):
    from byol_tpu.optim.factory import extract_sgdm_state
    mesh, state, step = training
    # ``train_step`` donates its state: the fixture's stays whole
    state = jax.tree_util.tree_map(jnp.array, state)
    params0 = jax.device_get(state.params)
    batches = _batches(3)
    losses, first = [], None
    for i, b in enumerate(batches):
        state, metrics = step(state, shard_batch_to_mesh(dict(b), mesh))
        losses.append(float(metrics["loss_mean"]))
        if i == 0:
            first = jax.device_get(extract_sgdm_state(state.opt_state)[0])
            # four routing layers x (2 views x 4 sequences x 20 tokens):
            # top-2 of 8 experts, 2 held — every copy routed here is held
            assert float(metrics["_moe_rows_dropped"]) == 0.0
            assert 0.0 < float(metrics["_moe_rows_held"]) \
                < 4 * 2 * BATCH * SEQ * 2
            assert "layer_loss_mean" not in metrics
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
    # the taps moved; the selection bias did not, in either
    moved = lambda tree, *path: float(np.linalg.norm(
        _at(tree, path) - _at(params0, path)))
    taps = ("backbone", "layer0", "shortconv", "conv")
    bias = ("backbone", "layer1", "moe", "e_score_correction_bias")
    final = jax.device_get(state.params)
    assert moved(final, *taps) > 0.0
    assert moved(final, *bias) == 0.0 == moved(want["params"], *bias)


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return np.asarray(tree)
