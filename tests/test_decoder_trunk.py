"""The decoder trunk (latent attention, a told-its-share expert layer,
hyper-connected residual streams) against the plain reference, on the CPU
in float32 at the tiny preset: hidden 64, 4 heads and 8 experts of which a
share of 2 holds half, top-2, 2 streams, 3 layers (1 dense + 2 sparse).

Tolerances.  Program and reference are two float32 implementations of the
same equations that differ in the ORDER of sums (sorted ragged products
against dense one-hot dispatch; the norm's division after the maps' product
against before it; fused views against one sequence at a time): features
and losses agree to a few float32 roundings (1e-5 relative); a gradient
leaf to 1e-3 of its norm, or, where its true gradient is structurally zero
(the stream-to-stream map of the first and the last sub-layer), to 1e-6 of
the largest leaf; parameters after three LARS steps to 2e-5 of their
change, because LARS divides by a gradient norm.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_decoder_trunk as reference
from benchmarks.lib import weights_decoder_trunk
from byol_tpu.core import config as config_lib
from byol_tpu.models import decoder_trunk as trunk_lib
from byol_tpu.optim import lars as lars_lib
from byol_tpu.parallel.compile_plan import build_plan
from byol_tpu.parallel.mesh import MeshSpec, build_mesh, shard_batch_to_mesh

TINY = trunk_lib.TINY
SEQ, BATCH = 16, 4
CONF = dict(                                   # the tiny preset, as a
    qk_nope_head_dim=16, qk_rope_head_dim=8,   # configuration file's keys
    v_head_dim=16, kv_lora_rank=16, num_experts_per_tok=2,
    routed_scaling_factor=2.0, norm_topk_prob=True, n_routed_experts=4,
    published={"n_routed_experts": 8}, layer_share="1/2", rms_norm_eps=1e-6,
    rope_theta=10000,
    rope_scaling=dict(factor=64, original_max_position_embeddings=16,
                      beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1),
    hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30)


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(seed, batch=BATCH, vocab=64):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, vocab, (batch, SEQ)), jnp.int32)


def _trunk(share="1/2", **kw):
    return trunk_lib.DecoderTrunk(TINY, trunk_lib.LayerShare.parse(share),
                                  **kw)


def _seeded(module, *args, seed=5):
    like = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), *args))["params"]
    return weights_decoder_trunk.make_weights(like, {}, seed)[0]


def _sizes(share="1/2"):
    return reference.sizes_of(dict(CONF, layer_share=share))


def _reference_features(params, tokens, share="1/2"):
    return jnp.stack([reference.trunk(params, t, _sizes(share))
                      for t in tokens])


@pytest.mark.parametrize("share,remat_policy", [
    ("1/2", "none"), ("1/2", "full"),
    # a quarter share: the expert layer gathers over twice its nominal
    # load and keeps the whole-size product for a step that exceeds it
    ("3/4", "full")])
def test_features_match_the_reference(share, remat_policy):
    tokens = _tokens(0, vocab=32)
    module = _trunk(share, remat_policy=remat_policy)
    params = _seeded(module, tokens)
    # each side ONE compiled program (op by op, the first case took minutes)
    got = jax.jit(lambda p: module.apply({"params": p}, tokens))(params)
    want = jax.jit(lambda p: _reference_features(p, tokens, share))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_every_gradient_leaf_matches_the_reference():
    tokens = _tokens(1)
    module = _trunk(remat_policy="full")
    params = _seeded(module, tokens)
    ct = jnp.asarray(np.random.default_rng(2).normal(size=(BATCH, 64)),
                     jnp.float32)
    got = jax.jit(jax.grad(lambda p: jnp.sum(
        module.apply({"params": p}, tokens) * ct)))(params)
    want = jax.jit(jax.grad(lambda p: jnp.sum(
        _reference_features(p, tokens) * ct)))(params)
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree_util.tree_leaves(want)
    largest = max(float(jnp.linalg.norm(w)) for w in flat_want)
    assert len(flat_got) == len(flat_want) > 80
    for (path, g), w in zip(flat_got, flat_want):
        gap = float(jnp.linalg.norm(g - w))
        assert gap <= 1e-3 * float(jnp.linalg.norm(w)) + 1e-6 * largest, \
            (jax.tree_util.keystr(path), gap, float(jnp.linalg.norm(w)))


@pytest.fixture(scope="module")
def training():
    """ONE set-up and ONE compiled step for the tests that drive it (the
    step donates its state: a test steps a copy)."""
    with jax.default_matmul_precision("highest"):
        return _training(telemetry="step")


def _copy(state):
    return jax.tree_util.tree_map(jnp.array, state)


def _training(share="1/2", telemetry="off", zero1=False):
    """The normal path: Config -> resolve -> mesh -> plan ->
    setup_training, at the tiny preset."""
    from byol_tpu.training.build import setup_training
    c = config_lib.Config()
    c = c.replace(
        task=dataclasses.replace(c.task, task="synth_tokens",
                                 batch_size=BATCH, epochs=4, seq_len=SEQ),
        model=dataclasses.replace(
            c.model, arch="decoder_trunk_tiny", head_latent_size=32,
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
        rcfg, mesh, jax.random.PRNGKey(0),
        plan=build_plan(mesh, zero1=zero1))
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
    params, target, stats = weights_decoder_trunk.make_weights(
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


def _slice_heads(attn, lo, hi, z):
    """The heads ``[lo, hi)`` of an uncut attention's parameters."""
    q_w, kv_w = z.qk_head_dim, z.qk_nope_head_dim + z.v_head_dim
    out = jax.tree_util.tree_map(lambda x: x, attn)
    out["q_b"] = {"kernel": attn["q_b"]["kernel"][:, lo * q_w:hi * q_w]}
    out["kv_b"] = {"kernel": attn["kv_b"]["kernel"][:, lo * kv_w:hi * kv_w]}
    out["o"] = {"kernel": attn["o"]["kernel"][
        lo * z.v_head_dim:hi * z.v_head_dim]}
    return out


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Over both shares of one layer: head slices summed, routed parts
    summed and the shared expert counted once give the uncut layer."""
    z = TINY
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, SEQ, 64)),
                    jnp.float32)
    whole_attn = trunk_lib.LatentAttention(z, z.num_attention_heads)
    whole_moe = trunk_lib.ExpertLayer(z, 0, z.n_routed_experts)
    p_attn = _seeded(whole_attn, x)
    p_moe = _seeded(whole_moe, x)
    uncut = _sizes("0/1")
    want_attn = jnp.stack([reference.latent_attention(p_attn, r, uncut,
                                                      "float32") for r in x])
    want_moe = jnp.stack([reference.expert_layer(p_moe, r, uncut,
                                                 "float32")[0] for r in x])
    shared = trunk_lib.GatedMLP(z.moe_intermediate_size).apply(
        {"params": p_moe["shared"]}, x)
    got_attn, got_routed = 0.0, 0.0
    for index in range(2):
        share = trunk_lib.LayerShare(index, 2)
        h_lo, h_n = share.held(z.num_attention_heads, "heads")
        e_lo, e_n = share.held(z.n_routed_experts, "experts")
        got_attn += trunk_lib.LatentAttention(z, h_n).apply(
            {"params": _slice_heads(p_attn, h_lo, h_lo + h_n, z)}, x)
        part = dict(p_moe, experts={k: v[e_lo:e_lo + e_n]
                                    for k, v in p_moe["experts"].items()})
        got_routed += trunk_lib.ExpertLayer(z, e_lo, e_n).apply(
            {"params": part}, x) - shared
    np.testing.assert_allclose(got_attn, want_attn, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got_routed + shared, want_moe, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("lo,held,favoured", [
    (4, 4, (5,)),        # half the experts held: one product size
    (6, 2, (6, 7)),      # a quarter held, every copy routed here: the load
])                       # passes twice the nominal one, the fallback runs
def test_no_row_is_dropped_when_the_router_sends_everything_here(
        lo, held, favoured):
    z = TINY
    x = jnp.asarray(np.random.default_rng(6).normal(size=(3, SEQ, 64)),
                    jnp.float32)
    layer = trunk_lib.ExpertLayer(z, lo, held)
    params = dict(_seeded(layer, x))
    params["e_score_correction_bias"] = jnp.zeros(8).at[
        jnp.asarray(favoured)].set(100.0)
    got, sown = layer.apply({"params": params}, x,
                            mutable=[trunk_lib.ROUTING])
    stats = sown[trunk_lib.ROUTING]["stats"][0]
    rows = x.shape[0] * SEQ
    assert float(stats[1]) == rows              # every row on a favoured one
    assert len(favoured) * rows <= float(stats[0]) <= 2 * rows
    assert float(stats[3]) == 0.0
    share = f"{lo // held}/{8 // held}"
    want = jnp.stack([reference.expert_layer(params, r, _sizes(share),
                                             "float32")[0] for r in x])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# Hand-made routing for the combine: 8 tokens x top-4, tokens with 0, 1, 2,
# 3 and k copies on the held experts, sorted as the layer sorts them.
COPIES = (0, 1, 2, 4, 0, 3, 1, 4)               # held copies of each token
CAPS = {"every": 32, "usual": 20, "short": 12}  # 15 held: 'short' cuts 3


def _routing(cap, experts=3, seed=11):
    """``idx, pos, ok, valid`` as ``ExpertLayer.product(cap)`` builds them."""
    rng = np.random.default_rng(seed)
    tokens, k = len(COPIES), 4
    here = np.zeros((tokens, k), bool)
    for t, n in enumerate(COPIES):
        here[t, rng.permutation(k)[:n]] = True
    bucket = np.where(here, rng.integers(0, experts, (tokens, k)),
                      experts).reshape(-1)
    order = np.argsort(bucket, kind="stable")
    place = np.argsort(order, kind="stable").reshape(tokens, k)
    held = int(here.sum())
    idx = (order // k)[:cap]
    ok = here & (place < cap)
    pos = np.minimum(place, cap - 1)
    assert (place >= cap).any() or cap == tokens * k    # something to clamp
    return idx, pos, ok, (np.arange(cap) < held)[:, None]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cap", sorted(CAPS))
def test_the_combine_adds_each_marked_copy_once(cap, dtype):
    """``_sum_copies`` / ``_put_rows`` against a float64 sum rounded once
    (bf16: to the bit) and a float32 sum in slot order (to the bit):
    tokens with 0, 1, 2, 3 and k copies, every copy in reach
    (``every``), the usual cut (``usual``: only copies held elsewhere lie
    past it) and a cut that loses held copies (``short``: clamped, masked)."""
    idx, pos, ok, _ = _routing(CAPS[cap])
    counts = ok.sum(1)
    assert {0, 1, 2, 4} <= set(counts.tolist())
    assert (counts.sum() < sum(COPIES)) == (cap == "short")
    rows = jnp.asarray(np.random.default_rng(12).normal(
        size=(CAPS[cap], 24)), jnp.dtype(dtype))
    want = np.zeros((len(COPIES), 24))
    in_order = np.zeros((len(COPIES), 24), np.float32)   # slot by slot
    for t, j in zip(*np.nonzero(ok)):
        want[t] += np.asarray(rows, np.float64)[pos[t, j]]
        in_order[t] += np.asarray(rows, np.float32)[pos[t, j]]
    for fn in (trunk_lib._sum_copies,
               lambda *a: trunk_lib._put_rows(a[0], idx, *a[1:])):
        got = jax.jit(fn)(rows, pos, ok)
        assert got.dtype == rows.dtype
        np.testing.assert_array_equal(np.asarray(got),
                                      in_order.astype(rows.dtype))
        if dtype == "bfloat16":      # four bf16 add exactly in float32
            np.testing.assert_array_equal(np.asarray(got),
                                          want.astype(rows.dtype))
        else:
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    x = jnp.asarray(np.random.default_rng(13).normal(
        size=(len(COPIES), 24)), rows.dtype)
    np.testing.assert_array_equal(
        np.asarray(trunk_lib._take_rows(x, idx, pos, ok)),
        np.asarray(x)[idx])


@pytest.mark.parametrize("cap", ["every", "usual"])
def test_dispatch_and_combine_are_each_others_transpose(cap):
    """``<take(x), r> = <x, put(r)>`` for rows ``r`` masked as the layer
    masks them, and each ``custom_vjp`` hands back the other's forward."""
    idx, pos, ok, valid = _routing(CAPS[cap])
    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.normal(size=(len(COPIES), 24)), jnp.float32)
    r = jnp.asarray(np.where(valid, rng.normal(size=(CAPS[cap], 24)), 0),
                    jnp.float32)
    rows, take_vjp = jax.vjp(
        lambda x: trunk_lib._take_rows(x, idx, pos, ok), x)
    out, put_vjp = jax.vjp(
        lambda r: trunk_lib._put_rows(r, idx, pos, ok), r)
    np.testing.assert_allclose(jnp.vdot(rows, r), jnp.vdot(x, out),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(take_vjp(r)[0]),
                                  np.asarray(out))
    np.testing.assert_array_equal(np.asarray(put_vjp(x)[0]),
                                  np.asarray(rows))
    # autodiff's own transpose of the gather (a scatter-add) agrees
    np.testing.assert_allclose(
        jax.vjp(lambda x: x[idx], x)[1](r)[0], out, rtol=1e-6, atol=1e-6)


def test_h_res_has_unit_row_and_column_sums():
    streams = tuple(jnp.asarray(np.random.default_rng(7 + j).normal(
        size=(2, SEQ, 64)), jnp.float32) for j in range(TINY.hc_mult))
    module = trunk_lib.HyperConnection(TINY)
    params = _seeded(module, streams)
    h_pre, h_post, h_res = module.apply({"params": params}, streams)
    assert h_res.shape == (2, SEQ, 2, 2) and float(h_res.min()) > 0.0
    np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(h_res.sum(-2), 1.0, atol=1e-4)
    assert 0.0 < float(h_pre.min()) and float(h_pre.max()) < 1.0
    assert 0.0 < float(h_post.min()) and float(h_post.max()) < 2.0


def test_lars_gives_two_experts_of_one_leaf_different_trust_ratios():
    rng = np.random.default_rng(8)
    params = {"moe": {"experts": {"gate": jnp.asarray(
        rng.normal(size=(2, 6, 5)), jnp.float32)},
        "router": jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)}}
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    grads["moe"]["experts"]["gate"] = grads["moe"]["experts"]["gate"].at[
        1].multiply(100.0)
    assert lars_lib.default_exclusion_mask(params)["moe"]["experts"][
        "gate"] == lars_lib.PER_EXPERT
    tx = lars_lib.scale_by_lars_trust_ratio()
    scaled, _ = tx.update(grads, tx.init(params), params)
    gate, p = scaled["moe"]["experts"]["gate"], params["moe"]["experts"][
        "gate"]
    for e in range(2):                       # each expert alone: 1e-3 |p|
        np.testing.assert_allclose(jnp.linalg.norm(gate[e]),
                                   1e-3 * jnp.linalg.norm(p[e]), rtol=1e-5)
    ratios = lars_lib.trust_ratio_vector(grads, params)
    assert ratios.shape == (3,) and float(ratios[0]) > 50 * float(ratios[1])
    # an expert's update does not depend on who shares its chip
    other = jax.tree_util.tree_map(lambda x: x, params)
    other["moe"]["experts"]["gate"] = p.at[1].multiply(7.0)
    again, _ = tx.update(grads, tx.init(other), other)
    np.testing.assert_array_equal(again["moe"]["experts"]["gate"][0],
                                  gate[0])


def test_lars_leaves_gains_biases_and_the_hyper_connection_values_alone():
    module = _trunk()
    tokens = _tokens(9)
    like = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), tokens))["params"]
    mask = lars_lib.default_exclusion_mask(like)
    hc = mask["layer1"]["ffn_hc"]
    assert not any(hc[k] for k in ("alpha_pre", "alpha_res", "b_pre",
                                   "b_post", "b_res", "scale"))
    assert hc["phi_pre"] is True and hc["phi_res"] is True
    moe = mask["layer1"]["moe"]
    assert moe["e_score_correction_bias"] is False and moe["router"] is True
    assert set(moe["experts"].values()) == {lars_lib.PER_EXPERT}
    assert mask["embed"]["embedding"] is True
    assert lars_lib.decay_mask(like)["layer1"]["moe"]["experts"][
        "down"] is True


def test_zero1_names_a_tree_with_an_expert_axis():
    """``--zero1 on`` flattens every leaf; LARS adapts each expert of a
    stacked kernel alone, so the build refuses the trunk by that name."""
    assert lars_lib.has_expert_axis({"experts": {"gate": lars_lib.PER_EXPERT},
                                     "kernel": True})
    assert not lars_lib.has_expert_axis({"kernel": True, "bias": False})
    with pytest.raises(ValueError, match="expert axis"):
        _training(zero1=True)


def test_the_step_stamps_the_trunks_scopes_and_counts_its_routing(training):
    rcfg, mesh, state, step = training
    batch = shard_batch_to_mesh(dict(_batches(1)[0]), mesh)
    with mesh:
        text = step.__wrapped__.lower(state, batch).as_text()
    for scope in trunk_lib.TRACE_SCOPES:
        assert scope in text.split('phase_scopes = "')[1].split('"')[0]
    _, metrics = step(_copy(state), batch)
    from byol_tpu.observability import health
    record = health.unpack(metrics["health"])
    # two routing layers x (2 views x 4 sequences x 16 positions) x top-2,
    # half the experts held: 256 copies expected, none dropped
    assert record["moe_rows_held"] == float(metrics["_moe_rows_held"]) > 100
    assert record["moe_rows_dropped"] == 0.0
    assert record["moe_load_max"] >= record["moe_load_mean"] > 0.0


def test_synth_token_views_repeat_from_a_seed_and_mask_independently():
    from byol_tpu.data.loader import get_loader
    c = config_lib.Config()
    c = c.replace(
        task=dataclasses.replace(c.task, task="synth_tokens", batch_size=8,
                                 seq_len=64, num_synth_samples=64),
        model=dataclasses.replace(c.model, arch="decoder_trunk_tiny",
                                  layer_share="0/2"),
        device=dataclasses.replace(c.device, num_replicas=1, seed=21))
    first = list(get_loader(c).train_loader)
    again = list(get_loader(c).train_loader)
    assert len(first) == 8 and first[0]["view1"].shape == (8, 64)
    for a, b in zip(first, again):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    v1 = np.concatenate([b["view1"] for b in first])
    v2 = np.concatenate([b["view2"] for b in first])
    mask_id = 64 - 1                       # 128 published rows / 2 chips
    assert v1.dtype == np.int32 and v1.max() <= mask_id and v1.min() >= 0
    m1, m2 = v1 == mask_id, v2 == mask_id
    assert 0.08 < m1.mean() < 0.22 and 0.08 < m2.mean() < 0.22
    assert (m1 != m2).any()
    np.testing.assert_array_equal(v1[~m1 & ~m2], v2[~m1 & ~m2])
    other = get_loader(c.replace(device=dataclasses.replace(
        c.device, seed=22)))
    assert (next(iter(other.train_loader))["view1"] != first[0]["view1"]).any()


def test_serve_refuses_a_token_backbone_with_one_line(capsys):
    from byol_tpu.serving.cli import main
    assert main(["--arch", "decoder_trunk_tiny"]) == 2
    err = capsys.readouterr().err.strip().splitlines()
    assert len(err) == 1 and "token" in err[0]


def test_a_token_backbone_refuses_pixels_and_an_image_backbone_tokens():
    from byol_tpu.training.build import build_net
    c = config_lib.Config()
    c = c.replace(task=dataclasses.replace(c.task, batch_size=4),
                  model=dataclasses.replace(c.model,
                                            arch="decoder_trunk_tiny"),
                  device=dataclasses.replace(c.device, num_replicas=1))
    with pytest.raises(ValueError, match="takes tokens input"):
        build_net(config_lib.resolve(
            c, num_train_samples=8, num_test_samples=4, output_size=10,
            input_shape=(32, 32, 3)))
    with pytest.raises(ValueError, match="not 'i/n'"):
        trunk_lib.LayerShare.parse("3")
    with pytest.raises(ValueError, match="do not divide"):
        trunk_lib.LayerShare(0, 3).held(8, "routed experts")
