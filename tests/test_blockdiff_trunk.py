"""The block-diffusion decoder trunk (every row ``[noised | clean]`` under the
three-part training mask of arXiv 2503.09573, a softmax router over experts
of which this chip holds a share, NO shared expert) against the plain
reference, on the CPU in float32 at the tiny preset: hidden 32, 2 layers, 4
query on 2 key/value heads of 16, blocks of 4 ids, tiles of 8 (two a half at
16 ids a sample), 8 experts top-3 — and the list of tile pairs with a kind
each that both lowerings of the core walk, against a brute-force mask.

Tolerances as tests/test_sparse_trunk.py: program and reference are two
float32 implementations of the same equations that differ in the ORDER of
sums (softmax over tiles of keys with a running max against the whole row;
sorted ragged products against a loop over experts; fused views against one
row at a time).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_blockdiff_trunk as reference
from benchmarks.lib import weights_sparse_trunk
from byol_tpu.core import config as config_lib
from byol_tpu.data import readers
from byol_tpu.models import decoder_trunk as trunk_lib
from byol_tpu.ops import attention
from byol_tpu.ops import causal_attention as kernels
from byol_tpu.optim import lars as lars_lib
from byol_tpu.parallel.compile_plan import build_plan
from byol_tpu.parallel.mesh import MeshSpec, build_mesh, shard_batch_to_mesh

TINY = trunk_lib.BLOCKDIFF_TINY
LENGTH, BATCH, SPAN = 16, 4, TINY.diffusion_block
ROW = 2 * LENGTH
SHARE = "1/4,vocab=2,heads=1"                  # 2 of 8 experts, 64 of 128 rows
CONF = dict(                                   # the tiny preset, as a
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,   # file's keys
    rope_theta=1e6, rms_norm_eps=1e-6, num_experts_per_tok=3,
    norm_topk_prob=True, num_experts=2, published={"num_experts": 8},
    layer_share=SHARE, block_length=SPAN)


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


# ---- the visibility rule: a list of tile pairs with a kind each ------------

def _brute_force(length, span):
    """The ``[2L, 2L]`` mask of a row ``[noised | clean]``, pair by pair
    from the four lines of the rule."""
    beta = np.arange(length) // span
    query, key = beta[:, None], beta[None, :]          # every (p, r)
    seen = np.zeros((2 * length, 2 * length), bool)
    seen[length:, length:] = key <= query              # clean on clean
    seen[:length, length:] = key < query               # noised on clean
    seen[:length, :length] = key == query              # noised on noised
    return seen                                        # clean on noised: never


def _expanded(tiles, block, rows):
    """What a list shows, as a ``[rows, rows]`` mask: every pair of a tile
    it lists by the tile's kind, nothing of a tile it does not list."""
    seen = np.zeros((rows, rows), bool)
    at = np.arange(block) // tiles.span
    kinds = {attention.FULL: np.ones((block, block), bool),
             attention.NOT_AFTER: at[None, :] <= at[:, None],
             attention.BEFORE: at[None, :] < at[:, None],
             attention.SAME: at[None, :] == at[:, None]}
    for i, j, kind in zip(tiles.q_of, tiles.k_of, tiles.kind):
        assert not seen[i * block:(i + 1) * block,
                        j * block:(j + 1) * block].any()      # listed once
        seen[i * block:(i + 1) * block, j * block:(j + 1) * block] = \
            kinds[kind]
    return seen


@pytest.mark.parametrize("blocks,block,span", [
    (2, 8, 4), (2, 8, 2), (3, 8, 1), (8, 512, 4), (1, 8, 4), (4, 4, 2)])
def test_the_list_of_tile_pairs_is_the_brute_force_mask(blocks, block, span):
    length = blocks * block
    tiles = attention.block_diffusion_tiles(blocks, span)
    want = _brute_force(length, span)
    np.testing.assert_array_equal(_expanded(tiles, block, 2 * length), want)
    # only tiles that hold a visible pair are listed, and all of them
    assert len(tiles.q_of) == blocks * blocks + 2 * blocks
    held = want.reshape(2 * blocks, block, 2 * blocks, block).any((1, 3))
    assert int(held.sum()) == len(tiles.q_of)
    assert int(want.sum()) == length * length + length * span
    # a query tile's pairs lie side by side and its own block comes last
    assert [i for i, _ in attention._tile_rows(tiles)] == list(
        range(2 * blocks))


def test_the_published_row_is_80_tile_pairs_of_256():
    tiles = attention.block_diffusion_tiles(8, 4)
    count = lambda kind: sum(k == kind for k in tiles.kind)
    assert len(tiles.q_of) == 80
    assert (count(attention.FULL), count(attention.NOT_AFTER),
            count(attention.BEFORE), count(attention.SAME)) == (56, 8, 8, 8)
    assert len(attention.causal_tiles(16).q_of) == 136   # masked-dense at 2L


@pytest.mark.parametrize("blocks", [1, 3, 8])
def test_block_length_one_with_no_noised_half_is_the_causal_list(blocks):
    """The clean half of the rule at ``span`` 1, its tiles renumbered from
    0, IS ``causal_tiles`` — and that list is the lower triangle."""
    tiles = attention.block_diffusion_tiles(blocks, 1)
    clean = [(i - blocks, j - blocks, kind) for i, j, kind in zip(
        tiles.q_of, tiles.k_of, tiles.kind) if i >= blocks]
    causal = attention.causal_tiles(blocks)
    assert clean == list(zip(causal.q_of, causal.k_of, causal.kind))
    np.testing.assert_array_equal(
        _expanded(causal, 8, 8 * blocks),
        np.tril(np.ones((8 * blocks, 8 * blocks), bool)))
    q_of, k_of = attention.causal_pairs(blocks)
    assert (list(causal.q_of), list(causal.k_of)) == (q_of.tolist(),
                                                      k_of.tolist())
    # the kernels read (i, j) alone there; any other list brings its flags
    assert kernels._flags(causal) is None
    flags = kernels._flags(tiles)
    assert len(flags) == len(tiles.q_of)
    first = [f & kernels.FIRST != 0 for f in flags]
    last = [f & kernels.LAST != 0 for f in flags]
    assert sum(first) == sum(last) == 2 * blocks
    assert [f & 3 for f in flags] == list(tiles.kind)
    for n, (i, j) in enumerate(zip(tiles.q_of, tiles.k_of)):
        assert first[n] == (n == 0 or tiles.q_of[n - 1] != i)
        assert last[n] == (i == j)          # a row's own block closes it


# ---- the core: both lowerings against the masked softmax -------------------

def _masked_softmax(q, k, v, seen, scale):
    """The oracle: one whole-row softmax under a ``[S, S]`` mask; ``q (B, Hq,
    S, D)`` on ``k, v (B, Hkv, S, D)``."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


def _core_and_oracle(q, k, v, tiles, block, seen):
    scale = q.shape[-1] ** -0.5

    def value_and_grads(fn):
        """ONE program a side: the interpreter runs op by op otherwise."""
        def loss(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

        def run(q, k, v):
            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (out,) + grads
        return jax.jit(run)
    core = value_and_grads(lambda q, k, v: (
        attention.blockwise_causal_attention(q, k, v, block=block,
                                             tiles=tiles)))
    oracle = value_and_grads(lambda q, k, v: _masked_softmax(
        q, k, v, seen, scale))
    return core(q, k, v), oracle(q, k, v)


def _qkv(seed, rows, heads, kv_heads, dim, batch=2):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (f(batch, heads, rows, dim), f(batch, kv_heads, rows, dim),
            f(batch, kv_heads, rows, dim))


@pytest.mark.parametrize("span", [1, 2, 4])
def test_the_jnp_body_is_the_masked_softmax_value_and_every_gradient(span):
    blocks, block = 2, 8
    q, k, v = _qkv(span, 2 * blocks * block, 4, 2, 16)
    got, want = _core_and_oracle(
        q, k, v, attention.block_diffusion_tiles(blocks, span), block,
        jnp.asarray(_brute_force(blocks * block, span)))
    for name, g, w in zip("out d_q d_k d_v".split(), got, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6, err_msg=name)


@pytest.mark.parametrize("span,dim,group", [(4, 128, 8), (1, 64, 2),
                                            (2, 64, 2)])
def test_the_kernels_are_the_masked_softmax_value_and_every_gradient(
        monkeypatch, span, dim, group):
    """The Pallas kernels under the interpreter at tiles of 128 (two a half,
    8 tile pairs a row: every kind among them), answered for by the test as
    tests/test_causal_attention_kernel.py does."""
    blocks, block = 2, 128
    tiles = attention.block_diffusion_tiles(blocks, span)
    assert set(tiles.kind) == {0, 1, 2, 3}
    q, k, v = _qkv(dim + span, 2 * blocks * block, group, 1, dim)
    monkeypatch.setattr(kernels, "applies", lambda *a, **kw: True)
    core = lambda q, k, v: attention.blockwise_causal_attention(
        q, k, v, block=block, tiles=tiles)
    assert "pallas_call" in str(jax.make_jaxpr(core)(q, k, v))
    got, want = _core_and_oracle(
        q, k, v, tiles, block, jnp.asarray(_brute_force(blocks * block,
                                                        span)))
    for name, g, w in zip("out d_q d_k d_v".split(), got, want):
        assert np.linalg.norm(g - w) <= 1e-5 * np.linalg.norm(w), name


def test_a_list_that_does_not_fit_the_rows_is_refused():
    q, k, v = _qkv(0, 40, 4, 2, 16)
    with pytest.raises(ValueError, match="tiles"):
        attention.blockwise_causal_attention(
            q, k, v, block=8, tiles=attention.block_diffusion_tiles(2, 4))
    with pytest.raises(ValueError, match="span"):
        attention.blockwise_causal_attention(
            q[:, :, :32], k[:, :, :32], v[:, :, :32], block=8,
            tiles=attention.block_diffusion_tiles(2, 3))


# ---- the layer and the trunk against the reference -------------------------

def _tokens(seed, batch=BATCH, vocab=64):
    """Rows ``[noised | clean]``, the mask id ``vocab - 1``."""
    rng = np.random.RandomState(seed)
    clean = rng.randint(0, vocab - 1, (batch, LENGTH)).astype(np.int32)
    return jnp.asarray(readers.noise_blocks(clean, rng, vocab - 1, SPAN))


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


def _layer(kernel_sizes):
    """The attention layer and a row of hidden states: the tiny preset's, or
    sizes the kernels take under the interpreter (tiles of 128, heads of
    64)."""
    if kernel_sizes:
        sizes = trunk_lib.GatedAttentionSizes(
            num_heads=4, num_kv_heads=2, head_dim=64, rotary_dim=64,
            rope_theta=1e6, block=128, output_gate=False)
        rows, hidden = 512, 32
    else:
        sizes, rows, hidden = TINY.gated_attention, ROW, 32
    layer = trunk_lib.GatedAttention(sizes, sizes.num_heads,
                                     sizes.num_kv_heads, 1e-6, jnp.float32,
                                     False, SPAN)
    x = jnp.asarray(np.random.default_rng(8).normal(size=(2, rows, hidden)),
                    jnp.float32)
    z = dict(_sizes(), heads=sizes.num_heads, kv_heads=sizes.num_kv_heads,
             head_dim=sizes.head_dim)
    return layer, x, z


@pytest.mark.parametrize("kernel_sizes", [False, True])
def test_the_attention_layer_matches_the_reference_on_both_lowerings(
        monkeypatch, kernel_sizes):
    """Positions ``0 .. L-1`` twice, the three-part mask, head norms, no
    gate: value and every gradient leaf — the ``jax.numpy`` body at the tiny
    preset, the kernels (interpreted) at tiles of 128."""
    layer, x, z = _layer(kernel_sizes)
    monkeypatch.setattr(kernels, "applies", lambda *a, **kw: kernel_sizes)
    like = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))
    params = _seeded({"layer0": {"blockdiff": like["params"]}})["layer0"][
        "blockdiff"]
    ct = jnp.asarray(np.random.default_rng(9).normal(size=x.shape),
                     jnp.float32)
    program = lambda p, x: jnp.sum(layer.apply({"params": p}, x) * ct)
    plain = lambda p, x: jnp.sum(jnp.stack([
        reference.attention(p, row, z, "float32") for row in x]) * ct)
    assert ("pallas_call" in str(jax.make_jaxpr(program)(params, x))) \
        is kernel_sizes
    got = jax.jit(jax.value_and_grad(program, argnums=(0, 1)))(params, x)
    want = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(params, x)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    assert _leafwise_close(got[1], want[1]) == 7      # q k v o, two norms, x


def test_the_leaking_twin_fails_the_mask_and_the_reference(monkeypatch):
    """``<=`` for ``<`` in the noised-on-clean rule (the benchmark's broken
    twin): its list is not the brute-force mask, and the layer under it is
    far from the reference."""
    tiles = attention.block_diffusion_tiles(2, SPAN)
    leaking = tiles._replace(kind=tuple(
        attention.NOT_AFTER if kind == attention.BEFORE else kind
        for kind in tiles.kind))
    shown = _expanded(leaking, 8, ROW) & ~_brute_force(LENGTH, SPAN)
    assert int(shown.sum()) == LENGTH * SPAN        # each its own clean block
    assert shown[:LENGTH, LENGTH:].sum() == shown.sum()
    layer, x, z = _layer(False)
    like = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))
    params = _seeded({"layer0": {"blockdiff": like["params"]}})["layer0"][
        "blockdiff"]
    want = jnp.stack([reference.attention(params, row, z, "float32")
                      for row in x])
    sound = layer.apply({"params": params}, x)
    monkeypatch.setattr(trunk_lib, "block_diffusion_tiles",
                        lambda blocks, span: leaking)
    broken = layer.apply({"params": params}, x)
    gap = lambda got: float(jnp.linalg.norm(got - want)
                            / jnp.linalg.norm(want))
    assert gap(sound) < 1e-5 < 0.05 < gap(broken)
    # the clean half does not see the leak: only noised queries do
    np.testing.assert_allclose(broken[:, LENGTH:], want[:, LENGTH:],
                               rtol=1e-4, atol=1e-5)


def _reference_trunk(params, tokens):
    return jnp.stack([reference.trunk(params, row, _sizes()) for row in
                      tokens])


def test_the_trunks_features_and_gradients_match_the_reference():
    tokens = _tokens(2, batch=2)
    assert tokens.shape == (2, ROW)
    trunk = _trunk(remat=True, remat_policy="full")
    like = jax.eval_shape(lambda: trunk.init(
        jax.random.PRNGKey(0), tokens))["params"]
    params = _seeded(like)
    ct = jnp.asarray(np.random.default_rng(3).normal(size=(2, 32)),
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
    assert _leafwise_close(got, want) == 2 + 2 * 12
    assert "shared" not in like["layer0"]["moe"]
    assert set(like["layer0"]) == {"attn_norm", "blockdiff", "ffn_norm",
                                   "moe"}
    # the representation reads the NOISED half alone: another clean half
    # under the same noised ids moves it (through attention), the pooling
    # of the clean rows themselves does not exist
    hidden = trunk.apply({"params": params}, tokens,
                         mutable=[trunk_lib.ROUTING])[0]
    assert hidden.shape == (2, 32)


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Four shares of 2 of the 8 experts: the routed parts summed give the
    uncut expert layer over all ``2 L`` positions of a row — nothing every
    chip computes alike rides along, the layer has no shared expert."""
    z = TINY
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, ROW, 32)),
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
    # what every chip computes alike is the attention layer, whole
    assert share.held(z.gated_attention.num_heads, "attention heads") == (
        0, 4)


# ---- the loader's view -------------------------------------------------------

def test_a_view_is_noised_then_clean_with_a_rate_a_block():
    """``readers.noise_blocks``: the clean half as given, the noised half
    the same ids or the mask id, a block of 4 masked at ONE rate — so whole
    blocks come out all masked and all clean far more often than positions
    drawn alone at the mean rate would — and the mask id never among the
    ids ``load_synth_tokens`` draws."""
    vocab, length = 64, 4096
    ids, _ = readers.load_synth_tokens(8, length, vocab, seed=1)
    assert ids.max() == vocab - 2                # vocab - 1 is the mask id
    rng = np.random.RandomState(0)
    view = readers.noise_blocks(ids, rng, vocab - 1, 4)
    assert view.shape == (8, 2 * length) and view.dtype == np.int32
    np.testing.assert_array_equal(view[:, length:], ids)
    noised = view[:, :length]
    masked = noised == vocab - 1
    np.testing.assert_array_equal(noised[~masked], ids[~masked])
    assert 0.47 < masked.mean() < 0.53           # E[t] = 1/2
    per_block = masked.reshape(8, length // 4, 4).sum(-1)
    # P(all four | t) = t^4, E = 1/5, each count 0..4 alike; drawn alone at
    # 1/2 a position all four would come 1/16 of the time
    for count in range(5):
        assert 0.17 < (per_block == count).mean() < 0.23, count
    again = readers.noise_blocks(ids, np.random.RandomState(0), vocab - 1, 4)
    np.testing.assert_array_equal(view, again)
    other = readers.noise_blocks(ids, rng, vocab - 1, 4)   # the second view
    assert not np.array_equal(other[:, :length], noised)
    np.testing.assert_array_equal(other[:, length:], ids)


def test_the_token_task_hands_a_block_diffusion_trunk_its_rows():
    from byol_tpu.data.loader import get_loader
    from byol_tpu.models.registry import get_spec
    assert get_spec("sdar_30b_a3b").diffusion_block == 4
    assert get_spec("blockdiff_trunk_tiny").diffusion_block == SPAN
    assert get_spec("keye_vl2_30b_a3b").diffusion_block == 0
    c = config_lib.Config()
    c = c.replace(
        task=dataclasses.replace(c.task, task="synth_tokens", batch_size=8,
                                 seq_len=LENGTH),
        model=dataclasses.replace(c.model, arch="blockdiff_trunk_tiny",
                                  layer_share=SHARE),
        device=dataclasses.replace(c.device, num_replicas=1))
    bundle = get_loader(c, num_synth_samples=32)
    assert bundle.input_shape == (ROW,)
    batch = next(bundle.make_train_iter(0))
    v1, v2 = batch["view1"], batch["view2"]
    assert v1.shape == v2.shape == (8, ROW)
    np.testing.assert_array_equal(v1[:, LENGTH:], v2[:, LENGTH:])
    assert v1[:, LENGTH:].max() < 63 and (v1[:, :LENGTH] == 63).any()
    assert not np.array_equal(v1[:, :LENGTH], v2[:, :LENGTH])
    plain = get_loader(c.replace(model=dataclasses.replace(
        c.model, arch="sparse_trunk_tiny")), num_synth_samples=32)
    assert plain.input_shape == (LENGTH,)


# ---- the normal path: Config -> resolve -> plan -> setup_training ----------

@pytest.fixture(scope="module")
def training():
    """ONE set-up and ONE compiled step for the tests that drive it (the
    step donates its state: a test steps a copy)."""
    from byol_tpu.training.build import setup_training
    with jax.default_matmul_precision("highest"):
        c = config_lib.Config()
        c = c.replace(
            task=dataclasses.replace(c.task, task="synth_tokens",
                                     batch_size=BATCH, epochs=4,
                                     seq_len=LENGTH),
            model=dataclasses.replace(
                c.model, arch="blockdiff_trunk_tiny", head_latent_size=32,
                projection_size=16, fuse_views=True, remat_policy="full",
                layer_share=SHARE),
            optim=dataclasses.replace(c.optim, warmup=1),
            device=dataclasses.replace(c.device, num_replicas=1, half=False,
                                       telemetry="step"))
        rcfg = config_lib.resolve(c, num_train_samples=4 * BATCH,
                                  num_test_samples=BATCH, output_size=10,
                                  input_shape=(ROW,))
        mesh = build_mesh(MeshSpec(data=1), jax.devices()[:1])
        net, state, step, _, _ = setup_training(
            rcfg, mesh, jax.random.PRNGKey(0), plan=build_plan(mesh))
        return net, mesh, state, step


def _copy(state):
    return jax.tree_util.tree_map(jnp.array, state)


def _batches(n, seed=3):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        clean = rng.randint(0, 63, (BATCH, LENGTH)).astype(np.int32)
        out.append({"view1": readers.noise_blocks(clean, rng, 63, SPAN),
                    "view2": readers.noise_blocks(clean, rng, 63, SPAN),
                    "label": rng.randint(0, 10, (BATCH,)).astype(np.int32)})
    return out


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


def test_the_step_stamps_the_blockdiff_scopes_and_lars_knows_every_leaf(
        training):
    _, mesh, state, step = training
    batch = shard_batch_to_mesh(dict(_batches(1)[0]), mesh)
    with mesh:
        text = step.__wrapped__.lower(state, batch).as_text()
    stamped = text.split('phase_scopes = "')[1].split('"')[0].split()
    for scope in trunk_lib.BLOCKDIFF_SCOPES:
        assert scope in stamped
    assert trunk_lib.BLOCKDIFF_SCOPES[:2] == ("blockdiff", "blockdiff/core")
    assert not {"mla", "gqa", "dsa", "moe/shared/x"} & set(stamped)
    like = jax.eval_shape(lambda: _trunk().init(
        jax.random.PRNGKey(0), _tokens(9)))["params"]
    mixer = lars_lib.default_exclusion_mask(like)["layer0"]["blockdiff"]
    for name in ("q", "k", "v", "o"):
        assert mixer[name]["kernel"] is True, name
    assert mixer["q_norm"]["scale"] is False
    assert mixer["k_norm"]["scale"] is False
    # the mask is a constant of the configuration: it sows no counter
    _, sown = _trunk().apply(
        {"params": jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, x.dtype), like)}, _tokens(9),
        mutable=True)
    assert set(sown) - {"params"} == {trunk_lib.ROUTING}


def test_the_published_sizes_build_the_parameters_the_config_implies():
    """``--trunk-depth 0+5`` of SDAR-30B-A3B at the cell's share: 94.64 M a
    layer (16 experts 75.50, attention 18.87, router 0.26), 38.90 M of
    embedding, 525.7 M with the heads."""
    from byol_tpu.models.registry import get_backbone, get_spec
    trunk, dim = get_backbone("sdar_30b_a3b", dtype=jnp.bfloat16,
                              layer_share="0/8,vocab=8,heads=1",
                              trunk_depth="0+5", remat_policy="full")
    assert dim == 2048 and get_spec("sdar_30b_a3b").vocab_size == 151936
    assert trunk.sizes.num_hidden_layers == 5 and trunk.vocab_rows == 18992
    assert trunk.trace_scopes == trunk_lib.BLOCKDIFF_SCOPES
    assert [trunk.sizes.mixer(i) for i in range(5)] == ["blockdiff"] * 5
    like = jax.eval_shape(lambda: trunk.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 2048), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree_util.tree_leaves(tree))
    assert count(like["embed"]) == 18992 * 2048
    layer = like["layer0"]
    assert count(layer["moe"]["experts"]) == 16 * 3 * 2048 * 768
    assert layer["moe"]["router"].shape == (2048, 128)
    assert count(layer["blockdiff"]) == 2 * 2048 * 4096 + 2 * 2048 * 512 \
        + 2 * 128
    assert count(layer) == 94_638_336
    assert count(like) == 5 * 94_638_336 + 18992 * 2048 + 2048
    with pytest.raises(ValueError, match="noised"):      # 3 tiles: no halves
        jax.eval_shape(lambda p: trunk.apply(
            {"params": p}, jnp.zeros((1, 1536), jnp.int32)), like)
