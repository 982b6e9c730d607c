"""The decoder-hybrid-decoder trunk (SambaY, arXiv 2507.06607: Mamba's
selective scan, differential attention under a band, in full and as cross
attention on an earlier layer's keys and values, gated memory units on an
earlier layer's scan output, every layer a dense SwiGLU, LayerNorm, no
position anywhere) on the CPU in float32 at ``SAMBAY_TINY``: hidden 64, 4
query on 2 key/value heads of 16, state 4, a window of 8 keys at tiles of 8
and 32 positions, 12 published layers.  The oracle is a plain forward kept
HERE, one row at a time, dense ``[S, S]`` masks, the scan a step a
position, what a layer hands on passed by name."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.core import config as config_lib
from byol_tpu.core import remat as remat_lib
from byol_tpu.models import decoder_trunk as trunk_lib
from byol_tpu.optim import lars as lars_lib
from byol_tpu.parallel.compile_plan import build_plan
from byol_tpu.parallel.mesh import MeshSpec, build_mesh, shard_batch_to_mesh

TINY = trunk_lib.SAMBAY_TINY
SEQ, BATCH = 32, 4
SHARE = "1/2,heads=1"                           # 64 of 128 vocabulary rows
CUT = "5-11"      # swa, ssm, diff, gmu, xattn, gmu, xattn: two readers each


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


# ---- the oracle ----------------------------------------------------------------

def layer_norm(x, p, eps=1e-5):
    centred = x - x.mean(-1, keepdims=True)
    return centred / jnp.sqrt((centred ** 2).mean(-1, keepdims=True) + eps) \
        * p["scale"] + p["bias"]


def plain_mamba(p, x, z):
    inner, n, r = 2 * x.shape[1], z.state, z.dt_rank
    mixed = x @ p["in_proj"]["kernel"]
    a, gate = mixed[:, :inner], mixed[:, inner:]
    padded = jnp.pad(a, ((z.conv_taps - 1, 0), (0, 0)))
    conv = sum(padded[j:j + len(a)] * p["taps"][j]
               for j in range(z.conv_taps))
    u = jax.nn.silu(conv + p["conv_bias"])
    low = u @ p["x_proj"]["kernel"]
    delta = jax.nn.softplus(low[:, :r] @ p["dt_proj"]["kernel"]
                            + p["dt_bias"])
    decay, state, rows = -jnp.exp(p["A_log"]), jnp.zeros((inner, n)), []
    for t in range(len(x)):
        state = jnp.exp(delta[t][:, None] * decay) * state \
            + (delta[t] * u[t])[:, None] * low[t, r:r + n][None]
        rows.append(state @ low[t, r + n:] + p["D"] * u[t])
    m = jnp.stack(rows)
    return (m * jax.nn.silu(gate)) @ p["out_proj"]["kernel"], m


def plain_attention(p, x, z, index, window, kv):
    s, (h, hkv, dh) = len(x), (z.num_heads, z.num_kv_heads, z.head_dim)
    if kv is None:
        qkv = x @ p["qkv"]["kernel"]
        q = qkv[:, :h * dh]
        k = qkv[:, h * dh:(h + hkv) * dh].reshape(s, hkv // 2, 2, dh)
        v = qkv[:, (h + hkv) * dh:].reshape(s, hkv // 2, 2 * dh)
    else:
        q, (k, v) = x @ p["q"]["kernel"], kv
    q = q.reshape(s, h // 2, 2, dh)
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = (ahead >= 0) & ((ahead < window) if window else True)
    lambda_0 = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = jnp.exp(p["lambda_q1"] @ p["lambda_k1"]) \
        - jnp.exp(p["lambda_q2"] @ p["lambda_k2"]) + lambda_0
    pairs = []
    for i in range(h // 2):
        j = i // (h // hkv)
        softmax = lambda c: jax.nn.softmax(jnp.where(
            seen, q[:, i, c] @ k[:, j, c].T / math.sqrt(dh), -jnp.inf), -1)
        o = softmax(0) @ v[:, j] - lam * (softmax(1) @ v[:, j])
        o = o / jnp.sqrt((o ** 2).mean(-1, keepdims=True) + 1e-5) \
            * p["subln"]["scale"]
        pairs.append((1 - lambda_0) * o)
    return jnp.concatenate(pairs, -1) @ p["o"]["kernel"], (k, v)


def plain_layer(p, x, handed, mixer, index, z):
    h, handed = layer_norm(x, p["attn_norm"]), dict(handed)
    if mixer == "ssm":
        mixed, handed["m"] = plain_mamba(p["ssm"], h, z)
    elif mixer == "gmu":
        gate = jax.nn.silu(h @ p["gmu"]["in_proj"]["kernel"])
        mixed = (handed["m"] * gate) @ p["gmu"]["out_proj"]["kernel"]
    else:
        mixed, kv = plain_attention(
            p["diff"], h, z, index, z.window if mixer == "swa" else 0,
            (handed["k"], handed["v"]) if mixer == "xattn" else None)
        if mixer == "diff":
            handed["k"], handed["v"] = kv
    x = x + mixed
    f = p["ffn"]
    g = layer_norm(x, p["ffn_norm"])
    return x + (jax.nn.silu(g @ f["gate"]["kernel"])
                * (g @ f["up"]["kernel"])) @ f["down"]["kernel"], handed


def plain_trunk(params, tokens, sizes):
    def row(ids):
        x, handed = params["embed"]["embedding"][ids], {}
        for i in range(sizes.num_hidden_layers):
            x, handed = plain_layer(
                params[f"layer{i}"], x, handed, sizes.mixer(i),
                sizes.published_index(i), sizes.hybrid_decoder)
        return layer_norm(x, params["final_norm"]).mean(0)
    return jnp.stack([row(ids) for ids in tokens])


# ---- the program against it -----------------------------------------------------

def _tokens(seed=0, batch=BATCH, vocab=64):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, vocab, (batch, SEQ)).astype(np.int32))


def _trunk(cut=CUT, **kw):
    sizes = TINY.cut(cut) if cut else TINY
    return trunk_lib.DecoderTrunk(sizes, trunk_lib.LayerShare.parse(SHARE),
                                  **kw)


def _seeded(trunk, seed=5):
    """Every leaf off its starting point, so that no term is zero."""
    like = trunk.init(jax.random.PRNGKey(seed), _tokens())["params"]
    leaves, treedef = jax.tree_util.tree_flatten(like)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
        for x, k in zip(leaves, keys)])


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


@pytest.mark.parametrize("remat_policy", ["none", "full"])
def test_the_trunks_features_and_gradients_match_the_plain_forward(
        remat_policy):
    trunk = _trunk(remat_policy=remat_policy)
    params, tokens = _seeded(trunk), _tokens()
    cotangent = jax.random.normal(jax.random.PRNGKey(2), (BATCH, 64))
    got = jax.jit(jax.value_and_grad(lambda p: jnp.sum(
        trunk.apply({"params": p}, tokens) * cotangent)))(params)
    want = jax.jit(jax.value_and_grad(lambda p: jnp.sum(
        plain_trunk(p, tokens, trunk.sizes) * cotangent)))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    assert _leafwise_close(got[1], want[1]) == len(
        jax.tree_util.tree_leaves(params))
    # every leaf takes a gradient: none is cut off from the loss
    assert all(float(jnp.linalg.norm(g)) > 0
               for g in jax.tree_util.tree_leaves(got[1]))


def test_the_roles_follow_the_published_index():
    kinds = trunk_lib.hybrid_decoder_mixers(32)
    assert [kinds.count(k) for k in ("ssm", "swa", "diff", "gmu",
                                     "xattn")] == [9, 8, 1, 7, 7]
    assert kinds[14:20] == ("ssm", "swa", "ssm", "diff", "gmu", "xattn")
    cut = trunk_lib.PHI4_MINI_FLASH.cut("15-19")
    assert cut.layer_mixers == ("swa", "ssm", "diff", "gmu", "xattn")
    assert cut.layer_index == (15, 16, 17, 18, 19)
    assert (cut.num_hidden_layers, cut.first_k_dense_replace) == (5, 5)
    assert [cut.published_index(i) for i in range(5)] == [15, 16, 17, 18, 19]
    assert trunk_lib.lambda_init(17) == pytest.approx(
        0.8 - 0.6 * math.exp(-5.1))
    assert TINY.cut(CUT).layer_mixers == (
        "swa", "ssm", "diff", "gmu", "xattn", "gmu", "xattn")
    # 'D+S' as before: every layer of this trunk is dense
    assert TINY.cut("3+0").layer_mixers == ("ssm", "swa", "ssm")
    assert TINY.with_depth(3, 0) == TINY.cut("3+0") == TINY.with_layers(0, 2)
    for bad in ("5", "a-b", "5-12", "9-5", "1+2+3"):
        with pytest.raises(ValueError):
            TINY.cut(bad)
    with pytest.raises(ValueError, match="uncut"):
        TINY.cut("5-9").cut("0-1")


def test_a_cut_that_leaves_the_exporting_layer_out_says_so():
    trunk = _trunk(cut="8-9")                          # gmu, xattn alone
    with pytest.raises(ValueError, match="hands one on"):
        trunk.init(jax.random.PRNGKey(0), _tokens())


def _layers(sizes):
    share = trunk_lib.LayerShare.parse(SHARE)
    return [trunk_lib.TrunkLayer(sizes, share, True, jnp.float32,
                                 sizes.mixer(i), sizes.published_index(i))
            for i in range(sizes.num_hidden_layers)]


def test_a_cut_keeps_every_layers_published_role_and_lambda():
    """Layers 5..9 of the UNCUT tiny, fed the uncut model's activations at
    layer 5's input, are the cut trunk's layers — the same parameters give
    the same streams — and the embedding's held rows are the uncut
    table's."""
    uncut, cut = _trunk(cut=""), _trunk(cut="5-9")
    params, tokens = _seeded(uncut), _tokens()
    assert uncut.vocab_rows == cut.vocab_rows == 64
    _, kept = uncut.apply(
        {"params": params}, tokens, mutable=["intermediates"],
        capture_intermediates=lambda m, _: isinstance(m,
                                                      trunk_lib.TrunkLayer))
    out_of = lambda i: kept["intermediates"][f"layer{i}"]["__call__"][0]
    streams, carried = out_of(4)[0], {}
    for n, layer in enumerate(_layers(cut.sizes)):
        streams, carried = layer.apply(
            {"params": params[f"layer{5 + n}"]}, streams, carried)
    np.testing.assert_allclose(streams[0], out_of(9)[0][0], rtol=1e-5,
                               atol=1e-6)
    # ... and lambda_0 is the published layer's: as layer 0 it is not
    shifted = dataclasses.replace(cut.sizes, layer_index=())
    wrong, _ = _layers(shifted)[0].apply(
        {"params": params["layer5"]}, out_of(4)[0], {})
    assert float(jnp.max(jnp.abs(wrong[0] - out_of(5)[0][0]))) > 1e-3
    cut_params = dict({f"layer{n}": params[f"layer{5 + n}"]
                       for n in range(5)}, embed=params["embed"],
                      final_norm=params["final_norm"])
    like = cut.init(jax.random.PRNGKey(0), tokens)["params"]
    assert jax.tree_util.tree_map(jnp.shape, like) == \
        jax.tree_util.tree_map(jnp.shape, cut_params)


def test_a_carried_tensors_cotangent_is_the_sum_over_its_readers():
    """Layer by layer BY HAND: the cotangent that reaches the exporting
    layer for ``m`` (``k``, ``v``) is the sum of what each of its two
    readers returns, and with it the exporting layer's gradient is the
    whole trunk's under the remat wrap."""
    trunk = _trunk(remat_policy="full")
    sizes, params, tokens = trunk.sizes, _seeded(trunk), _tokens()
    cotangent = jax.random.normal(jax.random.PRNGKey(3), (BATCH, SEQ, 64))
    final = trunk_lib.LayerNorm(1e-5, name="final_norm")

    def whole(p):
        _, kept = trunk.apply(
            {"params": p}, tokens, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "final_norm")
        return jnp.sum(kept["intermediates"]["final_norm"]["__call__"][0]
                       * cotangent)
    want = jax.grad(whole)(params)
    layers = _layers(sizes)
    streams, carried, backward = (params["embed"]["embedding"][tokens],), \
        {}, []
    for n, layer in enumerate(layers):
        (streams, carried), vjp = jax.vjp(
            lambda p, s, c, layer=layer: layer.apply({"params": p}, s, c),
            params[f"layer{n}"], streams, carried)
        backward.append(vjp)
    _, vjp = jax.vjp(lambda x: final.apply(
        {"params": params["final_norm"]}, x), streams[0])
    ct_streams = (vjp(cotangent)[0],)
    ct_carried = jax.tree_util.tree_map(jnp.zeros_like, carried)
    returned = {name: [] for name in ("m", "k", "v")}
    for n in reversed(range(len(layers))):
        arriving = ct_carried
        grads, ct_streams, ct_carried = backward[n]((ct_streams, arriving))
        for name in ct_carried:         # what THIS layer's reading returns
            own = ct_carried[name] - arriving[name]
            if float(jnp.max(jnp.abs(own))) > 0:
                returned[name].append((sizes.mixer(n), own))
        if sizes.mixer(n) in ("ssm", "diff"):
            # the exporter: what arrives for its tensors is the readers' sum
            for name in ("m",) if sizes.mixer(n) == "ssm" else ("k", "v"):
                np.testing.assert_allclose(
                    arriving[name], sum(own for _, own in returned[name]),
                    rtol=1e-6)
            _leafwise_close(grads, want[f"layer{n}"])
    assert [kind for kind, _ in returned["m"]] == ["gmu", "gmu"]
    assert [kind for kind, _ in returned["k"]] == ["xattn", "xattn"]
    assert [kind for kind, _ in returned["v"]] == ["xattn", "xattn"]


def test_the_scopes_are_one_lookup_by_mixer_set():
    share = trunk_lib.LayerShare()
    scopes = lambda z: trunk_lib.DecoderTrunk(z, share).trace_scopes
    assert scopes(TINY) == scopes(TINY.cut("5-9")) == trunk_lib.SAMBAY_SCOPES
    assert scopes(trunk_lib.PHI4_MINI_FLASH) == trunk_lib.SAMBAY_SCOPES
    for sizes, want in (
            (trunk_lib.TINY, trunk_lib.TRACE_SCOPES),
            (trunk_lib.LATENT_TINY, trunk_lib.TRACE_SCOPES),
            (trunk_lib.HYBRID_TINY, trunk_lib.HYBRID_SCOPES),
            (trunk_lib.SPARSE_TINY, trunk_lib.SPARSE_SCOPES),
            (trunk_lib.SHORTCONV_TINY, trunk_lib.SHORTCONV_SCOPES),
            (trunk_lib.SHORTCONV_TINY.with_depth(1, 4),
             trunk_lib.SHORTCONV_SCOPES),
            (trunk_lib.BLOCKDIFF_TINY, trunk_lib.BLOCKDIFF_SCOPES),
            (trunk_lib.XING4_29B_A4B, trunk_lib.TRACE_SCOPES),
            (trunk_lib.QWEN3_NEXT_80B_A3B.with_depth(0, 6),
             trunk_lib.HYBRID_SCOPES),
            (trunk_lib.LFM2_24B_A2B.with_depth(1, 4),
             trunk_lib.SHORTCONV_SCOPES)):
        assert scopes(sizes) == want
    assert trunk_lib.SAMBAY_SCOPES == (
        "ssm", "ssm/proj", "ssm/conv", "ssm/scan", "ssm/gate", "diff",
        "diff/core", "gmu", "ffn")


def test_lars_leaves_the_new_leaves_alone():
    like = jax.eval_shape(lambda: _trunk(cut="5-9").init(
        jax.random.PRNGKey(0), _tokens()))["params"]
    mask = lars_lib.default_exclusion_mask(like)
    ssm = mask["layer1"]["ssm"]
    assert like["layer1"]["ssm"]["A_log"].shape == (128, 4)
    assert like["layer1"]["ssm"]["taps"].shape == (4, 128)
    for name in ("A_log", "taps", "D", "dt_bias", "conv_bias"):
        assert ssm[name] is False, name
    for name in ("in_proj", "x_proj", "dt_proj", "out_proj"):
        assert ssm[name]["kernel"] is True, name
    for layer, names in (("layer0", ("qkv", "o")), ("layer4", ("q", "o"))):
        diff = mask[layer]["diff"]
        assert all(diff[n]["kernel"] is True for n in names)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            assert diff[name] is False
        assert diff["subln"]["scale"] is False
    assert set(like["layer4"]["diff"]) == {
        "q", "o", "subln", "lambda_q1", "lambda_k1", "lambda_q2",
        "lambda_k2"}                                   # no key, no value
    assert mask["layer3"]["gmu"]["in_proj"]["kernel"] is True
    for norm in ("attn_norm", "ffn_norm"):
        assert mask["layer0"][norm] == {"scale": False, "bias": False}
    assert mask["final_norm"] == {"scale": False, "bias": False}
    assert lars_lib.decay_mask(like)["layer1"]["ssm"]["A_log"] is False


# ---- the normal path: Config -> resolve -> plan -> setup_training ----------

@pytest.fixture(scope="module")
def training():
    """ONE set-up and ONE compiled step (the step donates its state: a test
    steps a copy)."""
    from byol_tpu.training.build import setup_training
    with jax.default_matmul_precision("highest"):
        c = config_lib.Config()
        c = c.replace(
            task=dataclasses.replace(c.task, task="synth_tokens",
                                     batch_size=BATCH, epochs=4,
                                     seq_len=SEQ),
            model=dataclasses.replace(
                c.model, arch="sambay_tiny", head_latent_size=32,
                projection_size=16, fuse_views=True, remat_policy="full",
                layer_share=SHARE, trunk_depth="5-9"),
            optim=dataclasses.replace(c.optim, warmup=1),
            device=dataclasses.replace(c.device, num_replicas=1, half=False,
                                       telemetry="step"))
        rcfg = config_lib.resolve(c, num_train_samples=4 * BATCH,
                                  num_test_samples=BATCH, output_size=10,
                                  input_shape=(SEQ,))
        mesh = build_mesh(MeshSpec(data=1), jax.devices()[:1])
        net, state, step, _, _ = setup_training(
            rcfg, mesh, jax.random.PRNGKey(0), plan=build_plan(mesh))
        return net, mesh, state, step


def _batch(seed=3):
    rng = np.random.RandomState(seed)
    draw = lambda: rng.randint(0, 64, (BATCH, SEQ)).astype(np.int32)
    return {"view1": draw(), "view2": draw(),
            "label": rng.randint(0, 10, (BATCH,)).astype(np.int32)}


def test_the_step_runs_without_an_expert_layer_and_reports_its_counters(
        training):
    from byol_tpu.observability import health
    from byol_tpu.training.steps import SOWN
    net, mesh, state, step = training
    assert net.backbone.trace_scopes == trunk_lib.SAMBAY_SCOPES
    state = jax.tree_util.tree_map(jnp.array, state)
    losses = []
    for _ in range(3):
        state, metrics = step(state, shard_batch_to_mesh(_batch(), mesh))
        losses.append(float(metrics["loss_mean"]))
    assert all(np.isfinite(losses))
    assert not [k for k in metrics if k.startswith(("_moe_", "_sel_"))]
    assert 0 < float(metrics["_ssm_dt_mean"]) < float(
        metrics["_ssm_dt_max"])
    assert 0 < float(metrics["_ssm_decay_min"]) < 1
    # the mean over three layers of exp(.) - exp(.) + lambda_0, near the
    # lambda_0 of published layers 5, 7 and 9 at the initialiser's vectors
    expect = np.mean([trunk_lib.lambda_init(i) for i in (5, 7, 9)])
    assert float(metrics["_diff_lambda_mean"]) == pytest.approx(expect,
                                                                abs=0.2)
    got = health.unpack(np.asarray(metrics["health"]))
    assert got["ssm_dt_max"] == pytest.approx(float(metrics["_ssm_dt_max"]))
    assert got["diff_lambda_mean"] == pytest.approx(
        float(metrics["_diff_lambda_mean"]))
    assert got["moe_rows_held"] == 0.0
    assert trunk_lib.STATE_SPACE in SOWN and trunk_lib.DIFFERENTIAL in SOWN
    # what the trunk sows: the two collections, no routing
    _, sown = net.backbone.apply(
        {"params": jax.device_get(state.params)["backbone"]},
        jnp.asarray(_batch()["view1"]), mutable=True)
    assert set(sown) - {"params"} == {trunk_lib.STATE_SPACE,
                                      trunk_lib.DIFFERENTIAL}


def test_the_step_stamps_the_trunks_scopes(training):
    _, mesh, state, step = training
    batch = shard_batch_to_mesh(_batch(), mesh)
    with mesh:
        text = step.__wrapped__.lower(state, batch).as_text()
    stamped = text.split('phase_scopes = "')[1].split('"')[0].split()
    for scope in trunk_lib.SAMBAY_SCOPES:
        assert scope in stamped
    assert not {"mla", "gqa", "moe/route", "mhc"} & set(stamped)


def test_the_published_sizes_build_the_parameters_the_config_implies():
    """``--trunk-depth 15-19`` of Phi-4-mini-flash-reasoning at the cell's
    share: 98.3, 119.9, 98.3, 104.9 and 91.8 M a layer, 64.0 M of
    embedding, 577 M before the heads."""
    from byol_tpu.models.registry import get_backbone, get_spec
    trunk, dim = get_backbone("phi4_mini_flash", dtype=jnp.bfloat16,
                              layer_share="0/8,heads=1",
                              trunk_depth="15-19", remat_policy="full")
    assert dim == 2560 and get_spec("phi4_mini_flash").vocab_size == 200064
    assert trunk.sizes.num_hidden_layers == 5 and trunk.vocab_rows == 25008
    like = jax.eval_shape(lambda: trunk.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1024), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree_util.tree_leaves(tree))
    ffn, norms = 3 * 2560 * 10240, 4 * 2560
    attention = 2560 * 5120 + 2560 * 2560 + 4 * 64 + 128
    assert count(like["embed"]) == 25008 * 2560
    assert count(like["layer0"]) == count(like["layer2"]) \
        == ffn + norms + attention
    assert count(like["layer1"]["ssm"]) == 2560 * 10240 + 5120 * 192 \
        + 160 * 5120 + 5120 * 2560 + 5120 * 16 + 4 * 5120 + 3 * 5120
    assert count(like["layer3"]["gmu"]) == 2 * 2560 * 5120
    assert count(like["layer4"]["diff"]) == 2 * 2560 * 2560 + 4 * 64 + 128
    assert "ROUTING" not in like and not any(
        "moe" in like[f"layer{i}"] for i in range(5))
    assert count(like) == pytest.approx(577.2e6, rel=1e-3)
