"""The sparse-attention decoder trunk (grouped-query attention whose softmax
runs over the keys a learned indexer picks, the indexer's own KL loss, a
softmax router over experts of which this chip holds a share, NO shared
expert) against the plain reference, on the CPU in float32 at the tiny
preset: hidden 32, 2 layers, 4 query on 2 key/value heads of 16, 2 index
heads of 8, 6 keys a query, blocks of 8 (three a row at 20 tokens, the last
short), 8 experts top-3.

Tolerances as tests/test_hybrid_trunk.py: program and reference are two
float32 implementations of the same equations that differ in the ORDER of
sums (softmax over blocks of keys with a running max against the whole row;
a threshold built bit by bit against a full sort; sorted ragged products
against a loop over experts; fused views against one sequence at a time).
A SET is all or nothing: two index scores closer than their rounding would
flip a key between the two — none is at these seeds.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_sparse_trunk as reference
from benchmarks.lib import weights_sparse_trunk
from byol_tpu.core import config as config_lib
from byol_tpu.models import decoder_trunk as trunk_lib
from byol_tpu.ops import key_selection
from byol_tpu.ops.attention import (blockwise_causal_attention,
                                    causal_pairs, kept_probabilities,
                                    selected_attention)
from byol_tpu.optim import lars as lars_lib
from byol_tpu.parallel.compile_plan import build_plan
from byol_tpu.parallel.mesh import MeshSpec, build_mesh, shard_batch_to_mesh

TINY = trunk_lib.SPARSE_TINY
SIZES = TINY.sparse_attention
SEQ, BATCH = 20, 4
SHARE = "1/4,vocab=2,heads=1"                  # 2 of 8 experts, 64 of 128 rows
CONF = dict(                                   # the tiny preset, as a
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,   # file's keys
    rope_theta=1e7, rms_norm_eps=1e-6, num_experts_per_tok=3,
    norm_topk_prob=True, num_experts=2, published={"num_experts": 8},
    layer_share=SHARE,
    sa_config=dict(indexer_num_heads=2, indexer_head_dim=8,
                   indexer_num_kv_heads=1, topk=6, q_chunk_size=8,
                   kv_chunk_size=8))
SOWN = [trunk_lib.ROUTING, trunk_lib.SELECTION, trunk_lib.LAYER_LOSS]


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


def _seeded(like, seed=5):
    # the weights' rules read a leaf's place in the WHOLE tree
    return weights_sparse_trunk.make_weights(
        {"backbone": like}, {}, seed)[0]["backbone"]


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


# ---- the selection and the core, alone ------------------------------------

def _tiles(square, block):
    """``(B, S, S)`` -> the tile layout of ops/attention.py, a last short
    block filled with zeros."""
    batch, seq, _ = square.shape
    blocks = -(-seq // block)
    whole = np.zeros((batch,) + (blocks * block,) * 2, square.dtype)
    whole[:, :seq, :seq] = square
    cut = lambda i: slice(i * block, (i + 1) * block)
    return jnp.asarray(np.stack([whole[:, cut(i), cut(j)]
                                 for i, j in zip(*causal_pairs(blocks))]))


def _square(tiles, seq):
    """Tiles as one ``(B, S, S)`` array, zero above the diagonal blocks."""
    tiles = np.asarray(tiles)
    block = tiles.shape[-1]
    blocks = -(-seq // block)
    whole = np.zeros((tiles.shape[1],) + (blocks * block,) * 2, tiles.dtype)
    cut = lambda i: slice(i * block, (i + 1) * block)
    for tile, i, j in zip(tiles, *causal_pairs(blocks)):
        whole[:, cut(i), cut(j)] = tile
    return whole[:, :seq, :seq]


def _core(q, k, v, selected, block):
    """``selected_attention`` on sequences filled up to whole blocks."""
    seq = q.shape[2]
    whole = lambda x: jnp.pad(x, [(0, 0), (0, 0), (0, -seq % block), (0, 0)])
    out, lse = selected_attention(whole(q), whole(k), whole(v), selected,
                                  block=block)
    return out[:, :, :seq], lse


def _index(seed, batch, seq, ties):
    rng = np.random.default_rng(seed)
    index = rng.normal(size=(batch, seq, seq)).astype(np.float32)
    if ties:              # exact zeros of either sign, and repeated values
        index[:, :, ::3] = 0.0
        index[0, :, 1::4] = -0.0
        index[-1] = np.round(index[-1])
    return index


@pytest.mark.parametrize("seq,block,topk,ties", [
    (20, 8, 6, False),     # topk inside the first block
    (20, 8, 6, True),
    (24, 8, 16, True),     # on a block's edge: two blocks keep everything
    (19, 5, 7, True),      # nothing divides anything
    (12, 4, 1, True)])     # one key a query
def test_a_query_keeps_exactly_its_largest_causal_keys_ties_to_the_left(
        seq, block, topk, ties):
    batch = 2
    index = _index(seq + topk, batch, seq, ties)
    masks = key_selection.select_top_keys(_tiles(index, block), topk,
                                          block=block)
    got = _square(masks, seq)
    for b in range(batch):
        for t in range(seq):
            keep = sorted(range(t + 1),
                          key=lambda s: (-float(index[b, t, s]), s))
            want = np.zeros(seq, bool)
            want[keep[:min(t + 1, topk)]] = True
            assert (got[b, t] == want).all(), (b, t)
    # the reference's full sort says the same
    causal = np.tril(np.ones((seq, seq), bool))
    for b in range(batch):
        np.testing.assert_array_equal(
            reference.selected_keys(jnp.asarray(index[b]), causal, topk),
            got[b])
    counts = key_selection.pair_counts(masks, seq)
    assert float(counts[0]) == batch * seq * (seq + 1) // 2
    assert float(counts[1]) == got.sum() == batch * sum(
        min(t + 1, topk) for t in range(seq))


def _qkv(seed, batch=2, heads=4, kv_heads=2, seq=SEQ, dim=16):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return f(batch, heads, seq, dim), f(batch, kv_heads, seq, dim), \
        f(batch, kv_heads, seq, dim)


def _dense_masked(q, k, v, keep):
    """The whole-row softmax over the kept keys; also the probabilities."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    weights = jax.nn.softmax(
        jnp.where(keep[:, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v), weights


def test_with_every_key_kept_the_core_is_blockwise_causal_attention():
    q, k, v = _qkv(0)
    everything = key_selection.select_top_keys(
        _tiles(_index(1, 2, SEQ, False), 8), SEQ, block=8)      # topk >= S
    np.testing.assert_array_equal(
        _square(everything, SEQ),
        np.broadcast_to(np.tril(np.ones((SEQ, SEQ), bool)), (2, SEQ, SEQ)))
    loss = lambda core: lambda q, k, v: jnp.sum(jnp.sin(core(q, k, v)))
    want = jax.value_and_grad(loss(lambda q, k, v: blockwise_causal_attention(
        q, k, v, block=8)), argnums=(0, 1, 2))(q, k, v)
    got = jax.value_and_grad(loss(lambda q, k, v: _core(
        q, k, v, everything, 8)[0]), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_the_core_over_a_selection_is_the_masked_softmax_forward_and_back():
    q, k, v = _qkv(2)
    index = _index(3, 2, SEQ, True)
    index[1] += 10.0 * np.arange(SEQ)           # sequence 1: the latest keys
    masks = key_selection.select_top_keys(_tiles(index, 8), 6, block=8)
    keep = jnp.asarray(_square(masks, SEQ))
    # some query's own position is NOT among its keys, and some tile holds
    # no kept key of some row: the running max starts masked there
    assert not bool(jnp.all(keep[:, jnp.arange(SEQ), jnp.arange(SEQ)]))
    assert not bool(jnp.all(jnp.any(keep[:, 16:, :8], axis=-1)))
    loss = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))
    got = jax.value_and_grad(loss(lambda q, k, v: _core(
        q, k, v, masks, 8)[0]), argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(loss(lambda q, k, v: _dense_masked(
        q, k, v, keep)[0]), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)
    whole = lambda x: jnp.pad(x, [(0, 0), (0, 0), (0, 4), (0, 0)])
    probs = _square(kept_probabilities(
        whole(q), whole(k), _core(q, k, v, masks, 8)[1], masks, block=8),
        SEQ)
    np.testing.assert_allclose(
        probs, jnp.mean(_dense_masked(q, k, v, keep)[1], axis=1),
        rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(probs.sum(axis=-1), 1.0, rtol=1e-5)


# ---- the layer, the trunk, the step ---------------------------------------

def _layer_and_weights(seed=5):
    layer = trunk_lib.SparseAttention(SIZES)
    x = jnp.asarray(np.random.default_rng(seed).normal(
        size=(3, SEQ, 32)), jnp.float32)
    like = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))
    params = _seeded({"layer0": {"dsa": like["params"]}}, seed)[
        "layer0"]["dsa"]
    return layer, params, x


def test_the_layers_output_and_index_loss_match_the_reference():
    layer, params, x = _layer_and_weights()
    got, sown = jax.jit(lambda p, x: layer.apply(
        {"params": p}, x, mutable=SOWN))(params, x)
    want = [jax.jit(lambda p, row: reference.sparse_attention(
        p, row, _sizes(), "float32"))(params, row) for row in x]
    np.testing.assert_allclose(got, jnp.stack([w[0] for w in want]),
                               rtol=1e-5, atol=1e-6)
    index_loss, = sown[trunk_lib.LAYER_LOSS]["index"]
    np.testing.assert_allclose(
        index_loss, sum(w[1] for w in want) / (len(x) * SEQ), rtol=1e-5)
    assert 0.01 < float(index_loss) < 5.0
    pairs, = sown[trunk_lib.SELECTION]["pairs"]
    assert pairs.tolist() == [3 * SEQ * (SEQ + 1) // 2,
                              3 * sum(min(t + 1, 6) for t in range(SEQ))]


def test_the_indexer_learns_from_its_loss_alone_and_the_trunk_not_from_it():
    layer, params, x = _layer_and_weights(7)

    def both(p, x):
        out, sown = layer.apply({"params": p}, x, mutable=SOWN)
        return jnp.sum(jnp.sin(out)), sown[trunk_lib.LAYER_LOSS]["index"][0]
    indexer = ("index_q", "index_k", "index_w")
    from_output = jax.jit(jax.grad(lambda p, x: both(p, x)[0],
                                   argnums=(0, 1)))(params, x)
    from_loss = jax.jit(jax.grad(lambda p, x: both(p, x)[1],
                                 argnums=(0, 1)))(params, x)
    norm = lambda tree: float(sum(jnp.sum(jnp.square(g)) for g in
                                  jax.tree_util.tree_leaves(tree)))
    for name in params:
        if name in indexer:
            assert norm(from_output[0][name]) == 0.0, name
            assert norm(from_loss[0][name]) > 0.0, name
        else:
            assert norm(from_output[0][name]) > 0.0, name
            assert norm(from_loss[0][name]) == 0.0, name
    assert norm(from_output[1]) > 0.0 and norm(from_loss[1]) == 0.0


def _reference_trunk(params, tokens, share=SHARE):
    rows = [reference.trunk(params, t, _sizes(share)) for t in tokens]
    return jnp.stack([r[0] for r in rows]), \
        sum(r[1] for r in rows) / len(rows)


def test_the_trunks_features_loss_and_gradients_match_the_reference():
    tokens = _tokens(2, batch=2)
    trunk = _trunk(remat=True, remat_policy="full")
    like = jax.eval_shape(lambda: trunk.init(
        jax.random.PRNGKey(0), tokens))["params"]
    params = _seeded(like)
    ct = jnp.asarray(np.random.default_rng(3).normal(size=(2, 32)),
                     jnp.float32)

    def program(p):
        feats, sown = trunk.apply({"params": p}, tokens, mutable=SOWN)
        layer_loss = sum(jax.tree_util.tree_leaves(
            sown[trunk_lib.LAYER_LOSS]))
        return jnp.sum(feats * ct) + layer_loss, (feats, layer_loss)

    def plain(p):
        feats, index_loss = _reference_trunk(p, tokens)
        return jnp.sum(feats * ct) + index_loss, (feats, index_loss)
    (_, (feats, layer_loss)), got = jax.jit(jax.value_and_grad(
        program, has_aux=True))(params)
    (_, (want_feats, want_loss)), want = jax.jit(jax.value_and_grad(
        plain, has_aux=True))(params)
    np.testing.assert_allclose(feats, want_feats, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(layer_loss, want_loss, rtol=1e-5)
    assert _leafwise_close(got, want) > 25
    assert "shared" not in like["layer0"]["moe"]


@pytest.fixture(scope="module")
def training():
    """ONE set-up and ONE compiled step for the tests that drive it (the
    step donates its state: a test steps a copy)."""
    with jax.default_matmul_precision("highest"):
        return _training(telemetry="step")


def _copy(state):
    return jax.tree_util.tree_map(jnp.array, state)


def _training(telemetry="off"):
    """The normal path: Config -> resolve -> mesh -> plan ->
    setup_training, at the tiny preset."""
    from byol_tpu.training.build import setup_training
    c = config_lib.Config()
    c = c.replace(
        task=dataclasses.replace(c.task, task="synth_tokens",
                                 batch_size=BATCH, epochs=4, seq_len=SEQ),
        model=dataclasses.replace(
            c.model, arch="sparse_trunk_tiny", head_latent_size=32,
            projection_size=16, fuse_views=True, remat_policy="full",
            layer_share=SHARE),
        optim=dataclasses.replace(c.optim, warmup=1),
        device=dataclasses.replace(c.device, num_replicas=1, half=False,
                                   telemetry=telemetry))
    rcfg = config_lib.resolve(c, num_train_samples=4 * BATCH,
                              num_test_samples=BATCH, output_size=10,
                              input_shape=(SEQ,))
    mesh = build_mesh(MeshSpec(data=1), jax.devices()[:1])
    net, state, step, _, _ = setup_training(
        rcfg, mesh, jax.random.PRNGKey(0), plan=build_plan(mesh))
    return net, mesh, state, step


def _batches(n, seed=3):
    rng = np.random.default_rng(seed)
    return [{"view1": rng.integers(0, 64, (BATCH, SEQ), dtype=np.int32),
             "view2": rng.integers(0, 64, (BATCH, SEQ), dtype=np.int32),
             "label": rng.integers(0, 10, (BATCH,)).astype(np.int32)}
            for _ in range(n)]


def test_the_references_expert_loads_are_the_programs_routing():
    """The rows each held expert of each layer is sent, by the reference's
    own count, against the program's counter of the rows it held."""
    tokens = _tokens(6)
    trunk = _trunk()
    like = jax.eval_shape(lambda: trunk.init(
        jax.random.PRNGKey(0), tokens))["params"]
    params = _seeded(like)
    rows = 0
    layer = jax.jit(lambda p, x: reference.trunk_layer(p, x, _sizes(),
                                                       "float32"))
    for sequence in tokens:
        x = params["embed"]["embedding"][sequence]
        for name in ("layer0", "layer1"):
            x, _, here = layer(params[name], x)
            assert here.shape == (2,)
            rows += int(here.sum())
    _, sown = jax.jit(lambda p: trunk.apply({"params": p}, tokens,
                                            mutable=SOWN))(params)
    stats = sum(jax.tree_util.tree_leaves(sown[trunk_lib.ROUTING]))
    assert rows == int(stats[0]) > 0


def test_three_optimizer_steps_match_the_reference(training):
    from byol_tpu.optim.factory import extract_sgdm_state
    _, mesh, state, step = training
    like = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (state.params, state.batch_stats))
    params, target, stats = weights_sparse_trunk.make_weights(
        *like, 11, copies=2)
    params0 = jax.device_get(params)
    state = _copy(state).replace(params=params, target_params=target,
                                 batch_stats=stats)
    batches = _batches(3)
    losses, index_losses, first = [], [], None
    for i, b in enumerate(batches):
        state, metrics = step(state, shard_batch_to_mesh(dict(b), mesh))
        losses.append(float(metrics["loss_mean"]))
        index_losses.append(float(metrics["layer_loss_mean"]))
        # the total is BYOL's loss, the probe's and what the layers sowed
        np.testing.assert_allclose(
            metrics["loss_mean"], metrics["byol_loss_mean"]
            + metrics["linear_loss_mean"] + metrics["layer_loss_mean"],
            rtol=1e-6)
        if i == 0:
            first = jax.device_get(extract_sgdm_state(state.opt_state)[0])
            assert float(metrics["_moe_rows_dropped"]) == 0.0
    hp = {"lr": 0.2, "weight_decay": 1e-6, "base_decay": 0.996,
          "global_batch": BATCH, "warmup_steps": 4, "total_steps": 16}
    want = reference.train_steps(params0, batches, hp, conf=CONF)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    np.testing.assert_allclose(index_losses, want["index_losses"], rtol=1e-4)
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
    # the indexer moved, by its own loss
    index_q = jax.device_get(state.params)["backbone"]["layer0"]["dsa"][
        "index_q"]["kernel"]
    assert np.linalg.norm(index_q - params0["backbone"]["layer0"]["dsa"][
        "index_q"]["kernel"]) > 0.0


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Four shares of 2 of the 8 experts: the routed parts summed give the
    uncut expert layer — nothing every chip computes alike rides along, the
    layer has no shared expert."""
    z = TINY
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, SEQ, 32)),
                    jnp.float32)
    whole = trunk_lib.ExpertLayer(z, 0, z.n_routed_experts)
    like = jax.eval_shape(lambda: whole.init(jax.random.PRNGKey(0), x))
    p_moe = _seeded({"layer0": {"moe": like["params"]}})["layer0"]["moe"]
    assert set(p_moe) == {"router", "experts"}
    want = jnp.stack([reference.expert_layer(p_moe, r, _sizes("0/1"),
                                             "float32")[0] for r in x])
    routed = 0.0
    for index in range(4):
        share = trunk_lib.LayerShare.parse(f"{index}/4,vocab=2,heads=1")
        lo, held = share.held(z.n_routed_experts, "routed experts")
        assert (lo, held) == (2 * index, 2)
        part = dict(p_moe, experts={k: v[lo:lo + held]
                                    for k, v in p_moe["experts"].items()})
        routed += trunk_lib.ExpertLayer(z, lo, held).apply(
            {"params": part}, x)
    np.testing.assert_allclose(routed, want, rtol=1e-4, atol=1e-5)
    # what every chip computes alike is the attention layer, whole: the
    # same module and weights whatever the share
    assert share.held(SIZES.num_heads, "attention heads") == (0, 4)


@pytest.mark.parametrize("sizes,builds", [
    (trunk_lib.SPARSE_TINY, False), (trunk_lib.HYBRID_TINY, True),
    (trunk_lib.TINY, True)])
def test_an_expert_layer_without_shared_experts_builds_no_shared_module(
        sizes, builds):
    x = jnp.zeros((1, 4, sizes.hidden_size), jnp.float32)
    like = jax.eval_shape(lambda: trunk_lib.ExpertLayer(sizes, 0, 2).init(
        jax.random.PRNGKey(0), x))["params"]
    assert ("shared" in like) == builds == bool(sizes.n_shared_experts)


def test_the_published_sizes_build_the_parameters_the_config_implies():
    from byol_tpu.models.registry import get_backbone, held_vocab_rows
    share = "0/8,vocab=8,heads=1"
    module, dim = get_backbone("keye_vl2_30b_a3b", layer_share=share,
                               trunk_depth="0+4")
    assert dim == 2048 and held_vocab_rows("keye_vl2_30b_a3b", share) == 18992
    assert module.trace_scopes == trunk_lib.SPARSE_SCOPES
    assert {module.sizes.mixer(i) for i in range(48)} == {"dsa"}
    like = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree_util.tree_leaves(tree))
    layer = like["layer0"]
    assert sorted(like) == ["embed", "final_norm"] + [
        f"layer{i}" for i in range(4)]
    assert count(layer["moe"]["experts"]) == 16 * 3 * 2048 * 768
    assert layer["moe"]["router"].shape == (2048, 128)
    # q 8.39 M, k + v 2.10 M, o 8.39 M, indexer 2.26 M, router 0.26 M, norms
    assert count(layer) - count(layer["moe"]["experts"]) == (
        2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * (16 * 64 + 64 + 16)
        + 2048 * 128 + 2 * 128 + 2 * 2048)
    assert count(like["embed"]) == 18992 * 2048
    assert 425e6 < count(like) < 428e6              # + heads and probe: 440 M


def test_lars_adapts_the_indexers_kernels_and_leaves_the_gains_alone():
    like = jax.eval_shape(lambda: _trunk().init(
        jax.random.PRNGKey(0), _tokens(9)))["params"]
    dsa = lars_lib.default_exclusion_mask(like)["layer0"]["dsa"]
    for name in ("q", "k", "v", "o", "index_q", "index_k", "index_w"):
        assert dsa[name]["kernel"] is True, name
    assert dsa["q_norm"]["scale"] is False and dsa["k_norm"]["scale"] is False


def test_the_step_stamps_dsa_counts_the_selection_and_averages_two_views(
        training):
    from byol_tpu.training.steps import _forward_views
    net, mesh, state, step = training
    host = _batches(1)[0]
    batch = shard_batch_to_mesh(dict(host), mesh)
    with mesh:
        text = step.__wrapped__.lower(state, batch).as_text()
    stamped = text.split('phase_scopes = "')[1].split('"')[0].split()
    for scope in trunk_lib.SPARSE_SCOPES:
        assert scope in stamped
    assert "mla" not in stamped and "gqa" not in stamped
    # unfused, each view's forward sows a mean over ITS rows: the step takes
    # their average, the same mean over all rows; the counters add
    sown = [jax.jit(lambda p, stats, fuse=fuse: _forward_views(
        net, p, stats, host["view1"], host["view2"], train=True, fuse=fuse,
        update_stats=False)[3])(state.params, state.batch_stats)
            for fuse in (True, False)]
    for name in (trunk_lib.LAYER_LOSS, trunk_lib.SELECTION):
        np.testing.assert_allclose(sown[1][name], sown[0][name], rtol=1e-5)
    _, metrics = step(_copy(state), batch)
    # two layers x (2 views x 4 sequences): every causal pair scored, six
    # keys a query kept
    rows = 2 * 2 * BATCH
    assert float(metrics["_sel_causal_pairs"]) == rows * SEQ * (SEQ + 1) // 2
    assert float(metrics["_sel_selected_pairs"]) == rows * sum(
        min(t + 1, 6) for t in range(SEQ))
    np.testing.assert_allclose(metrics["layer_loss_mean"],
                               sown[0][trunk_lib.LAYER_LOSS], rtol=1e-5)
