"""The patterned decoder trunk (Gated DeltaNet layers, a gated grouped-query
attention layer every ``full_attention_interval``-th, a softmax router over
experts of which this chip holds a share, a gated shared expert, zero-centred
norms, a plain residual) against the plain reference, on the CPU in float32
at the tiny preset: hidden 32, period 2 over 4 layers, 2 key / 4 value heads
of 8, 4 query on 2 key/value heads of 16 (rotary on 8), 8 experts top-3.

Tolerances.  Program and reference are two float32 implementations of the
same equations that differ in the ORDER of sums: the chunked (WY) rule with
a triangular solve against one scan step a token; softmax over blocks of
keys with a running max against the whole row; sorted ragged products
against a loop over experts; fused views against one sequence at a time.
Values agree to a few float32 roundings (1e-5 relative; 2e-5 for the rule
alone, whose triangular solve chains C substitutions); a gradient leaf to 1e-3 of
its norm (+ 1e-6 of the largest leaf); parameters after three LARS steps to
2e-5 of their change, because LARS divides by a gradient norm.  The same
comparisons FAIL by three orders of magnitude when the rule's products run
in bfloat16 (the last test of the rule): a lower precision does not pass.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_hybrid_trunk as reference
from benchmarks.lib import weights_hybrid_trunk
from byol_tpu.core import config as config_lib
from byol_tpu.models import decoder_trunk as trunk_lib
from byol_tpu.models import gated_delta
from byol_tpu.ops import delta_rule
from byol_tpu.ops.attention import (blockwise_causal_attention,
                                    dense_attention)
from byol_tpu.optim import lars as lars_lib
from byol_tpu.parallel.compile_plan import build_plan
from byol_tpu.parallel.mesh import MeshSpec, build_mesh, shard_batch_to_mesh

TINY = trunk_lib.HYBRID_TINY
SEQ, BATCH = 20, 4                             # 20: neither 8 nor 64 divides it
SHARE = "1/4,vocab=2,heads=1"                  # 2 of 8 experts, 64 of 128 rows
CONF = dict(                                   # the tiny preset, as a
    full_attention_interval=2,                 # configuration file's keys
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.5, rope_theta=1e7,
    rms_norm_eps=1e-6, num_experts_per_tok=3, norm_topk_prob=True,
    num_experts=2, published={"num_experts": 8}, layer_share=SHARE)


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


def _seeded(module, *args, seed=5):
    like = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), *args))["params"]
    # the weights' rules read a leaf's place in the WHOLE tree
    return weights_hybrid_trunk.make_weights(
        {"backbone": like}, {}, seed)[0]["backbone"]


def _sizes(share=SHARE):
    held = 8 // int(share.split(",")[0].split("/")[1])
    return reference.sizes_of(dict(CONF, layer_share=share,
                                   num_experts=held))


def _reference_features(params, tokens, share=SHARE):
    return jnp.stack([reference.trunk(params, t, _sizes(share))
                      for t in tokens])


@functools.lru_cache(maxsize=None)
def _features_case(share):
    """Tokens, seeded weights and the reference's features of one share:
    the same for every remat policy (ONE compiled program a share)."""
    tokens = _tokens(0)
    params = _seeded(_trunk(share), tokens)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: _reference_features(p, tokens, share))(
            params)
    return tokens, params, want


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


# ---- the rule, the convolution and the attention core, alone -------------

def _rule_inputs(seq, seed=0, batch=2, heads=3, dk=8, dv=6):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    k = f(batch, seq, heads, dk)
    return (f(batch, seq, heads, dk) * dk ** -0.5,
            k / jnp.linalg.norm(k, axis=-1, keepdims=True),
            f(batch, seq, heads, dv),
            -jnp.asarray(rng.uniform(0, 2, (batch, seq, heads)), jnp.float32),
            jnp.asarray(rng.uniform(0, 1, (batch, seq, heads)), jnp.float32))


@pytest.fixture(params=["jnp", "kernels"])
def rule_path(request, monkeypatch):
    """The rule's two lowerings: the ``jax.numpy`` path (what a CPU takes)
    and the within-chunk kernels of ops/delta_rule.py under the Pallas
    interpreter (``chunked_delta_rule`` chooses them from backend and
    shapes: the test answers for it)."""
    monkeypatch.setattr(delta_rule, "applies",
                        lambda *a, **k: request.param == "kernels")
    return request.param


def _per_token(*inputs):
    return jnp.stack([reference.delta_recurrence(*(x[i] for x in inputs),
                                                 "float32")
                      for i in range(inputs[0].shape[0])])


@pytest.mark.parametrize("seq,chunk", [
    (24, 8), (24, 4), (20, 8),      # 20: the last chunk is padded
    (24, 24), (24, 64),             # the whole sequence is one chunk
    (96, 32)])
def test_the_chunked_rule_is_the_per_token_recurrence(seq, chunk, rule_path):
    inputs = _rule_inputs(seq, seed=seq + chunk)
    got = gated_delta.chunked_delta_rule(*inputs, chunk=chunk)
    want = _per_token(*inputs)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    grads = jax.grad(loss(lambda *a: gated_delta.chunked_delta_rule(
        *a, chunk=chunk)), argnums=(0, 1, 2, 3, 4))(*inputs)
    wants = jax.grad(loss(_per_token), argnums=(0, 1, 2, 3, 4))(*inputs)
    for name, g, w in zip("q k v g beta".split(), grads, wants):
        assert float(jnp.linalg.norm(g - w)) <= 1e-4 * float(
            jnp.linalg.norm(w)), name


def test_the_rule_in_groups_of_sequences_is_the_rule(rule_path):
    inputs = _rule_inputs(24, seed=7, batch=4)
    loss = lambda **kw: lambda *a: jnp.sum(jnp.sin(
        gated_delta.chunked_delta_rule(*a, chunk=8, **kw)))
    whole = jax.value_and_grad(loss(), argnums=(0, 1, 2, 3, 4))(*inputs)
    for group in (1, 2, 3):            # 3 does not divide 4: one group
        got = jax.value_and_grad(loss(group=group),
                                 argnums=(0, 1, 2, 3, 4))(*inputs)
        np.testing.assert_allclose(got[0], whole[0], rtol=1e-6)
        for g, w in zip(got[1], whole[1]):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_the_rule_in_bfloat16_fails_the_float32_tolerance():
    inputs = _rule_inputs(24, seed=3)
    got = gated_delta.chunked_delta_rule(*inputs, chunk=8,
                                         dtype=jnp.bfloat16)
    want = _per_token(*inputs)
    assert got.dtype == jnp.bfloat16
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert 2e-3 < gap < 0.2            # a bf16 rounding, not a wrong rule


@pytest.mark.parametrize("side", [8, 32, 64, 128])
def test_the_triangular_inverse_and_its_backward(side):
    """Below ``SUBSTITUTED`` forward substitution alone; above it one, two
    rounds of block elimination on top; the backward from the inverse."""
    lower = jnp.tril(jnp.asarray(np.random.default_rng(side).normal(
        size=(3, side, side)) / side ** 0.5, jnp.float32), -1)
    whole = jnp.eye(side) + lower
    got = gated_delta.unit_lower_inverse(lower + jnp.triu(jnp.ones_like(
        lower)))                     # on and above the diagonal: not read
    np.testing.assert_allclose(got @ whole, jnp.broadcast_to(
        jnp.eye(side), whole.shape), atol=2e-5)
    loss = lambda fn: lambda a: jnp.sum(jnp.sin(fn(a)))
    grad = jax.grad(loss(gated_delta.unit_lower_inverse))(lower)
    want = jax.grad(loss(lambda a: jnp.linalg.inv(
        jnp.eye(side) + jnp.tril(a, -1))))(lower)
    np.testing.assert_allclose(grad, want, rtol=1e-4, atol=1e-4)


def test_the_rule_is_exact_where_a_power_series_of_the_system_cancels(
        rule_path):
    """One key repeated through the chunk, no decay, beta = 1: the system's
    strict lower triangle is all ones, whose powers grow like binomials
    (to 1e18 at 64) while its inverse is bidiagonal; forward substitution
    does not care."""
    seq = 64
    key = jnp.zeros((1, seq, 1, 8), jnp.float32).at[..., 0].set(1.0)
    value = jnp.asarray(np.random.default_rng(0).normal(size=(1, seq, 1, 6)),
                        jnp.float32)
    inputs = (key * 8 ** -0.5, key, value, jnp.zeros((1, seq, 1)),
              jnp.ones((1, seq, 1)))
    got = gated_delta.chunked_delta_rule(*inputs, chunk=64)
    np.testing.assert_allclose(got, _per_token(*inputs), rtol=1e-5,
                               atol=1e-5)


def test_the_causal_convolution_is_four_shifted_adds():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(3, SEQ, 10)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(4, 10)), jnp.float32)
    got = gated_delta.causal_conv(x, taps)
    want = jnp.stack([reference.shifted_conv(r, taps) for r in x])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # causal: a later token moves no earlier output; tap 3 meets the token
    moved = gated_delta.causal_conv(x.at[:, 7].add(1.0), taps)
    np.testing.assert_array_equal(moved[:, :7], got[:, :7])
    np.testing.assert_allclose(moved[:, 7] - got[:, 7],
                               jnp.broadcast_to(taps[3], (3, 10)), atol=1e-5)


@pytest.mark.parametrize("block", [4, 6, 8, 32])
def test_blockwise_grouped_attention_is_dense_attention_on_repeated_heads(
        block):
    rng = np.random.default_rng(block)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k, v = f(2, SEQ, 4, 16), f(2, SEQ, 2, 16), f(2, SEQ, 2, 16)
    cos, sin = trunk_lib.half_rotary_tables(1e7, 8, SEQ)
    heads_first = lambda t: t.transpose(0, 2, 1, 3)

    def both(fn):
        def run(q, k, v):
            q, k = (trunk_lib.apply_half_rotary(t, cos, sin) for t in (q, k))
            return fn(heads_first(q), heads_first(k), heads_first(v))
        return run
    got_fn = both(lambda q, k, v: blockwise_causal_attention(
        q, k, v, block=block))
    want_fn = both(lambda q, k, v: dense_attention(
        q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1), causal=True))
    np.testing.assert_allclose(got_fn(q, k, v), want_fn(q, k, v),
                               rtol=1e-5, atol=1e-5)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    for name, g, w in zip("qkv", jax.grad(loss(got_fn), (0, 1, 2))(q, k, v),
                          jax.grad(loss(want_fn), (0, 1, 2))(q, k, v)):
        assert float(jnp.linalg.norm(g - w)) <= 1e-5 * float(
            jnp.linalg.norm(w)), name


def test_half_rotary_turns_the_pairs_i_and_i_plus_half_and_leaves_the_rest():
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, SEQ, 2, 16)),
                    jnp.float32)
    cos, sin = trunk_lib.half_rotary_tables(1e7, 8, SEQ)
    got = trunk_lib.apply_half_rotary(x, cos, sin)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)   # angle 0
    want = jnp.stack([reference.half_rotary(r, _sizes()) for r in x])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(jnp.hypot(got[..., 1], got[..., 5]),
                               jnp.hypot(x[..., 1], x[..., 5]), rtol=1e-5)


# ---- the trunk against the reference -------------------------------------

@pytest.mark.parametrize("share,remat_policy", [
    ("0/1", "none"), ("0/1", "full"), (SHARE, "none"), (SHARE, "full")])
def test_features_match_the_reference(share, remat_policy):
    tokens, params, want = _features_case(share)
    module = _trunk(share, remat_policy=remat_policy)
    got = jax.jit(lambda p: module.apply({"params": p}, tokens))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@functools.lru_cache(maxsize=None)
def _gradient_case():
    """Tokens, seeded weights, a cotangent and the reference's gradient: ONE
    compiled program, whatever the remat policy it is compared with."""
    tokens = _tokens(1)
    params = _seeded(_trunk(), tokens)
    ct = jnp.asarray(np.random.default_rng(2).normal(size=(BATCH, 32)),
                     jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda p: jnp.sum(
            _reference_features(p, tokens) * ct)))(params)
    return tokens, params, ct, want


@pytest.mark.parametrize("remat_policy", ["none", "full"])
def test_every_gradient_leaf_matches_the_reference(remat_policy):
    tokens, params, ct, want = _gradient_case()
    module = _trunk(remat_policy=remat_policy)
    got = jax.jit(jax.grad(lambda p: jnp.sum(
        module.apply({"params": p}, tokens) * ct)))(params)
    assert _leafwise_close(got, want) > 60


@pytest.fixture(scope="module")
def training():
    """ONE set-up and ONE compiled step for the tests that drive it (the
    step donates its state: a test steps a copy)."""
    with jax.default_matmul_precision("highest"):
        return _training(telemetry="step")


def _copy(state):
    return jax.tree_util.tree_map(jnp.array, state)


def _training(share=SHARE, telemetry="off"):
    """The normal path: Config -> resolve -> mesh -> plan ->
    setup_training, at the tiny preset."""
    from byol_tpu.training.build import setup_training
    c = config_lib.Config()
    c = c.replace(
        task=dataclasses.replace(c.task, task="synth_tokens",
                                 batch_size=BATCH, epochs=4, seq_len=SEQ),
        model=dataclasses.replace(
            c.model, arch="hybrid_trunk_tiny", head_latent_size=32,
            projection_size=16, fuse_views=True, remat_policy="full",
            layer_share=share),
        optim=dataclasses.replace(c.optim, warmup=1),
        device=dataclasses.replace(c.device, num_replicas=1, half=False,
                                   telemetry=telemetry))
    rcfg = config_lib.resolve(c, num_train_samples=4 * BATCH,
                              num_test_samples=BATCH, output_size=10,
                              input_shape=(SEQ,))
    mesh = build_mesh(MeshSpec(data=1), jax.devices()[:1])
    _, state, step, _, _ = setup_training(
        rcfg, mesh, jax.random.PRNGKey(0), plan=build_plan(mesh))
    return rcfg, mesh, state, step


def _batches(n, seed=3):
    rng = np.random.default_rng(seed)
    return [{"view1": rng.integers(0, 64, (BATCH, SEQ), dtype=np.int32),
             "view2": rng.integers(0, 64, (BATCH, SEQ), dtype=np.int32),
             "label": rng.integers(0, 10, (BATCH,)).astype(np.int32)}
            for _ in range(n)]


def test_three_optimizer_steps_match_the_reference(training):
    from byol_tpu.optim.factory import extract_sgdm_state
    rcfg, mesh, state, step = training
    like = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (state.params, state.batch_stats))
    params, target, stats = weights_hybrid_trunk.make_weights(
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


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Four shares of 2 of the 8 experts: the routed parts summed, and what
    every chip computes alike — the mixer, the router, the shared expert
    behind its gate — counted once, give the uncut expert layer."""
    z = TINY
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, SEQ, 32)),
                    jnp.float32)
    whole = trunk_lib.ExpertLayer(z, 0, z.n_routed_experts)
    like = jax.eval_shape(lambda: whole.init(jax.random.PRNGKey(0), x))
    p_moe = weights_hybrid_trunk.make_weights(
        {"backbone": {"layer0": {"moe": like["params"]}}}, {}, 5)[0][
            "backbone"]["layer0"]["moe"]
    want = jnp.stack([reference.expert_layer(p_moe, r, _sizes("0/1"),
                                             "float32")[0] for r in x])
    # the shared part alone: a layer whose experts are all zero
    shared = whole.apply({"params": dict(p_moe, experts=jax.tree_util.tree_map(
        jnp.zeros_like, p_moe["experts"]))}, x)
    routed = 0.0
    for index in range(4):
        share = trunk_lib.LayerShare.parse(f"{index}/4,vocab=2,heads=1")
        lo, held = share.held(z.n_routed_experts, "routed experts")
        assert (lo, held) == (2 * index, 2)
        # heads are whole on every chip, the vocabulary is split over 2
        assert share.held(4, "attention heads") == (0, 4)
        assert share.held(128, "vocabulary rows") == (64 * (index % 2), 64)
        part = dict(p_moe, experts={k: v[lo:lo + held]
                                    for k, v in p_moe["experts"].items()})
        routed += trunk_lib.ExpertLayer(z, lo, held).apply(
            {"params": part}, x) - shared
    np.testing.assert_allclose(routed + shared, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("lo,held", [
    (5, 1),          # usual = every / 4: four whole slabs
    (2, 3)])         # usual = 3/4 of every: the second slab starts early
def test_the_fallback_in_slabs_drops_no_row_and_is_the_whole_product(
        lo, held, monkeypatch):
    """Every copy routed to the held experts, so the load passes twice the
    nominal one; with the size limit at zero the fallback runs in slabs."""
    z = TINY
    x = 2.0 + jnp.asarray(np.random.default_rng(6).normal(
        size=(2, SEQ, 32)), jnp.float32)
    layer = trunk_lib.ExpertLayer(z, lo, held)
    like = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))
    params = dict(weights_hybrid_trunk.make_weights(
        {"backbone": {"layer0": {"moe": like["params"]}}}, {}, 5)[0][
            "backbone"]["layer0"]["moe"])
    # x has a positive mean: a column of ones wins every token's softmax
    favoured = jnp.arange(lo, lo + 3) % 8
    params["router"] = params["router"].at[:, favoured].set(1.0)
    sizes = dict(_sizes("0/1"), first_expert=lo)
    want = jnp.stack([reference.expert_layer(params, r, sizes, "float32")[0]
                      for r in x])
    run = lambda: layer.apply({"params": params}, x,
                              mutable=[trunk_lib.ROUTING])
    whole, sown = run()
    stats = sown[trunk_lib.ROUTING]["stats"][0]
    every = x.shape[0] * SEQ * z.num_experts_per_tok
    assert float(stats[0]) == min(held, 3) * every / 3 > 2 * every * held / 8
    assert float(stats[3]) == 0.0
    monkeypatch.setattr(trunk_lib, "WHOLE_FALLBACK_BYTES", 0)
    slabs, _ = run()
    np.testing.assert_allclose(whole, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(slabs, want, rtol=1e-4, atol=1e-5)
    grad = lambda: jax.grad(lambda p: jnp.sum(jnp.sin(
        layer.apply({"params": p}, x, mutable=[trunk_lib.ROUTING])[0])))(
            params)
    in_slabs = grad()
    monkeypatch.setattr(trunk_lib, "WHOLE_FALLBACK_BYTES", 1 << 30)
    _leafwise_close(in_slabs, grad())


def test_the_softmax_routers_weights_sum_to_one_over_the_top_k():
    """Every expert the same matrices: the routed sum is that expert's
    output times the sum of the weights."""
    z = TINY
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, SEQ, 32)),
                    jnp.float32)
    layer = trunk_lib.ExpertLayer(z, 0, z.n_routed_experts)
    params = dict(layer.init(jax.random.PRNGKey(1), x)["params"])
    assert "e_score_correction_bias" not in params       # no selection bias
    params["experts"] = {k: jnp.broadcast_to(v[:1], v.shape)
                         for k, v in params["experts"].items()}
    one = {k: {"kernel": v[0]} for k, v in params["experts"].items()}
    gated = jax.nn.sigmoid(x @ params["shared_gate"]["kernel"])
    mlp = trunk_lib.GatedMLP(z.moe_intermediate_size)
    want = mlp.apply({"params": one}, x) + gated * mlp.apply(
        {"params": params["shared"]}, x)
    np.testing.assert_allclose(layer.apply({"params": params}, x), want,
                               rtol=1e-5, atol=1e-6)


def test_layer_i_is_attention_iff_i_plus_one_divides_by_the_interval():
    z = trunk_lib.QWEN3_NEXT_80B_A3B
    assert [i for i in range(48) if z.mixer(i) == "gqa"] == list(
        range(3, 48, 4))
    assert {z.mixer(i) for i in range(48)} == {"gdn", "gqa"}
    assert {trunk_lib.XING4_29B_A4B.mixer(i) for i in range(40)} == {"mla"}
    like = jax.eval_shape(lambda: _trunk().init(
        jax.random.PRNGKey(0), _tokens(0)))["params"]
    kinds = [("gqa" in like[f"layer{i}"], "gdn" in like[f"layer{i}"])
             for i in range(4)]
    assert kinds == [(False, True), (True, False)] * 2       # period 2
    assert "attn_hc" not in like["layer0"]                   # plain residual
    assert like["embed"]["embedding"].shape == (64, 32)


def test_a_share_names_what_divides_differently_and_refuses_nonsense():
    share = trunk_lib.LayerShare.parse("3/16,vocab=8,heads=1")
    assert share.held(512, "routed experts") == (96, 32)
    assert share.held(151936, "vocabulary rows") == (3 * 18992, 18992)
    assert share.held(16, "attention heads") == (0, 16)
    assert trunk_lib.LayerShare.parse("1/2") == trunk_lib.LayerShare(1, 2)
    for bad in ("0/16,vocab=5", "0/16,rows=8", "0/16,vocab", "4/4,heads=1"):
        with pytest.raises(ValueError, match="layer share"):
            trunk_lib.LayerShare.parse(bad)
    from byol_tpu.models.registry import held_vocab_rows
    assert held_vocab_rows("qwen3_next_80b_a3b", "0/16,vocab=8,heads=1") \
        == 18992


def test_lars_leaves_the_gates_and_gains_alone_and_adapts_each_expert():
    like = jax.eval_shape(lambda: _trunk().init(
        jax.random.PRNGKey(0), _tokens(9)))["params"]
    mask = lars_lib.default_exclusion_mask(like)
    gdn = mask["layer0"]["gdn"]
    assert not any(gdn[k] for k in ("A_log", "dt_bias", "scale"))
    assert gdn["conv"] is True and gdn["qkvz"]["kernel"] is True
    gqa = mask["layer1"]["gqa"]
    assert gqa["q_norm"]["scale"] is False and gqa["q"]["kernel"] is True
    assert mask["layer0"]["attn_norm"]["scale"] is False
    assert mask["final_norm"]["scale"] is False
    moe = mask["layer1"]["moe"]
    assert moe["router"] is True and moe["shared_gate"]["kernel"] is True
    assert set(moe["experts"].values()) == {lars_lib.PER_EXPERT}
    # each held expert its own trust ratio: 1e-3 |p_e| whatever the others
    rng = np.random.default_rng(8)
    params = {"moe": {"experts": {"up": jnp.asarray(
        rng.normal(size=(2, 6, 5)), jnp.float32)}}}
    grads = {"moe": {"experts": {"up": jnp.ones((2, 6, 5)).at[1].multiply(
        100.0)}}}
    tx = lars_lib.scale_by_lars_trust_ratio()
    scaled, _ = tx.update(grads, tx.init(params), params)
    for e in range(2):
        np.testing.assert_allclose(
            jnp.linalg.norm(scaled["moe"]["experts"]["up"][e]),
            1e-3 * jnp.linalg.norm(params["moe"]["experts"]["up"][e]),
            rtol=1e-5)


def test_the_step_stamps_gdn_and_gqa_and_counts_a_512_free_routing(training):
    rcfg, mesh, state, step = training
    batch = shard_batch_to_mesh(dict(_batches(1)[0]), mesh)
    with mesh:
        text = step.__wrapped__.lower(state, batch).as_text()
    stamped = text.split('phase_scopes = "')[1].split('"')[0].split()
    for scope in trunk_lib.HYBRID_SCOPES:
        assert scope in stamped
    assert "mla" not in stamped and "mhc" not in stamped
    _, metrics = step(_copy(state), batch)
    # four routing layers x (2 views x 4 sequences x 20 positions) x top-3,
    # a quarter of the experts held: 480 copies expected, none dropped
    assert 200 < float(metrics["_moe_rows_held"]) < 900
    assert float(metrics["_moe_rows_dropped"]) == 0.0
