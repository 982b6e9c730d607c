"""The weight update alone: ``training/steps.apply_update`` on hand-made
parameter trees against a numpy reference — no encoder, no forward.

``apply_update`` is everything of the train step after the gradients
exist: the optax chain (weight-decay fold-in, LARS trust ratio, momentum),
the parameter write, the EMA tick, Polyak averaging, the telemetry vector,
the counters.  It is ONE path for both state layouts: replicated it runs
on the shaped trees, under ZeRO-1 (``mesh8``) on flat 1/8 shards, zero
padded — so every case here runs in both and is held to the same float64
reference.  Whole-step parity of the two layouts stays in
tests/test_zero1.py and tests/test_checkpoint.py.
"""
import dataclasses
import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.core import config as config_lib
from byol_tpu.observability import events, health
from byol_tpu.optim import lars as lars_lib
from byol_tpu.optim.factory import (MOMENTUM_DECAY, build_optimizer,
                                    extract_sgdm_state)
from byol_tpu.parallel.compile_plan import build_plan
from byol_tpu.training import steps
from byol_tpu.training.state import TrainState, create_train_state

LR, WD, BASE_DECAY, TOTAL_STEPS, POLYAK = 0.2, 1e-2, 0.9, 10, 0.5

# leaf sizes deliberately not multiples of 8: every ZeRO-1 leaf is padded
TREES = {
    "mixed": {"conv": {"kernel": (3, 3, 2, 3)},
              "bn": {"scale": (3,), "bias": (3,)},
              "dense": {"kernel": (7, 5), "bias": (5,)}},
    "all_1d": {"bn": {"scale": (6,), "bias": (6,)}, "head": {"bias": (3,)}},
    # a stacked expert kernel (one ratio per expert), a router, a norm
    "experts": {"dense": {"kernel": (5, 3)},
                "moe": {"experts": {"gate": (4, 3, 6)},
                        "router": {"kernel": (3, 4)}},
                "norm": {"scale": (3,)}},
}


def _random_like(shapes, seed, scale=1.0):
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda s: (scale * rng.standard_normal(s)).astype(np.float32),
        shapes, is_leaf=lambda s: isinstance(s, tuple))


def _tx(params, optimizer="lars_momentum", fixed_mask=False, wd=WD):
    """The chain as training/build.py builds it: under ZeRO-1 the
    exclusion mask is fixed from the shaped tree (``fixed_mask``).
    ``<name>+clip`` value-clips the gradients first (``--clip``)."""
    mask = lars_lib.default_exclusion_mask(params) if fixed_mask else None
    name, _, clip = optimizer.partition("+")
    tx, _ = build_optimizer(
        name, base_lr=LR, global_batch_size=256, weight_decay=wd,
        total_units=TOTAL_STEPS, warmup_units=0, adapt_mask=mask,
        clip=0.05 if clip else 0.0)
    return tx


def _scfg(**kw):
    kw.setdefault("weight_decay", WD)
    return steps.StepConfig(total_train_steps=TOTAL_STEPS,
                            base_decay=BASE_DECAY, **kw)


class Update:
    """``apply_update`` jitted over one tree in one layout."""

    def __init__(self, tree, layout, mesh8, *, scfg, optimizer="lars_momentum",
                 polyak=False, layer_scopes=()):
        self.params0 = _random_like(TREES[tree], 0)
        self.target0 = _random_like(TREES[tree], 1)
        zero1 = layout == "zero1"
        self.tx = _tx(self.params0, optimizer, fixed_mask=zero1,
                      wd=scfg.weight_decay)
        state = create_train_state(
            {"params": jax.tree_util.tree_map(jnp.asarray, self.params0)},
            None if zero1 else self.tx,
            polyak_ema=POLYAK if polyak else 0.0)
        state = state.replace(target_params=jax.tree_util.tree_map(
            jnp.asarray, self.target0))
        self.plan = build_plan(mesh8, zero1=zero1)
        self.state, _ = self.plan.prepare_state(state, self.tx)
        self.fn = jax.jit(functools.partial(
            steps.apply_update, tx=self.tx, scfg=scfg,
            zero1_ctx=self.plan.zero1_context(), layer_scopes=layer_scopes))
        self.telemetry = scfg.telemetry != "off"
        self.shapes = TREES[tree]

    def grads(self, k):
        return _random_like(self.shapes, 100 + k, scale=0.1)

    def step(self, grads):
        self.state, metrics = self.fn(self.state, grads, {},
                                      self.metrics_in())
        return metrics

    def metrics_in(self):
        m = {"loss_mean": jnp.asarray(1.5, jnp.float32)}
        if self.telemetry:
            m.update(_collapse_feature_std=jnp.asarray(0.25, jnp.float32),
                     _collapse_cosine_mean=jnp.asarray(0.5, jnp.float32))
        return m

    def run(self, n):
        """``n`` updates; returns the CANONICAL (shaped) state and the
        last metrics."""
        metrics = None
        for k in range(n):
            metrics = self.step(self.grads(k))
        return self.plan.to_canonical(self.state), metrics


def reference(params, target, grads_seq, *, ema_pre, polyak=None, wd=WD,
              ema_step0=0):
    """float64: wd fold-in + trust ratio + momentum + write + EMA tick.
    Returns params, momentum, target, polyak, and the last step's applied
    ratios (adapted leaves in tree order, a stacked kernel's per expert)."""
    mask = lars_lib.default_exclusion_mask(params)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    p = [np.asarray(x, np.float64) for x in leaves]
    t = [np.asarray(x, np.float64)
         for x in jax.tree_util.tree_leaves(target)]
    uses = jax.tree_util.tree_leaves(mask)
    m = [np.zeros_like(x) for x in p]
    pk = None if polyak is None else [x.copy() for x in p]
    ratios, update = [], None
    for k, grads in enumerate(grads_seq):
        lr = LR * 0.5 * (1.0 + np.cos(np.pi * k / TOTAL_STEPS))
        tau = 1.0 - (1.0 - BASE_DECAY) * (
            np.cos(np.pi * (ema_step0 + k) / TOTAL_STEPS) + 1.0) / 2.0
        ratios, update, p_old = [], [], [x.copy() for x in p]
        for i, g in enumerate(jax.tree_util.tree_leaves(grads)):
            u = np.asarray(g, np.float64)
            if uses[i]:
                u = u + wd * p[i]
                axes = (tuple(range(1, u.ndim))
                        if uses[i] == lars_lib.PER_EXPERT else None)
                pn = np.sqrt(np.sum(p[i] ** 2, axis=axes, keepdims=True))
                un = np.sqrt(np.sum(u ** 2, axis=axes, keepdims=True))
                r = np.where((pn > 0) & (un > 0),
                             lars_lib.TRUST_COEFFICIENT_DEFAULT * pn
                             / np.where(un > 0, un, 1.0), 1.0)
                ratios.extend(np.ravel(r))
                u = u * r
            m[i] = MOMENTUM_DECAY * m[i] + u
            update.append(-lr * m[i])
            p[i] = p[i] + update[i]
        src = p_old if ema_pre else p
        t = [tau * a + (1.0 - tau) * b for a, b in zip(t, src)]
        if pk is not None:
            pk = [polyak * a + (1.0 - polyak) * b for a, b in zip(pk, p)]
    un = lambda xs: jax.tree_util.tree_unflatten(treedef, xs)
    return dict(params=un(p), momentum=un(m), target=un(t),
                polyak=None if pk is None else un(pk),
                ratios=np.asarray(ratios or [1.0]), update=un(update))


def _close(got, want, rtol=2e-5, atol=1e-6):
    g, w = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(g) == len(w)
    for a, b in zip(g, w):
        np.testing.assert_allclose(np.asarray(a, np.float64), b,
                                   rtol=rtol, atol=atol)


def _norm(tree):
    return np.sqrt(sum(np.sum(np.asarray(x, np.float64) ** 2)
                       for x in jax.tree_util.tree_leaves(tree)))


# a stacked expert kernel needs its expert axis: ZeRO-1 refuses that tree
CASES = [(layout, tree) for layout in ("replicated", "zero1")
         for tree in ("mixed", "all_1d")] + [("replicated", "experts")]


@pytest.mark.parametrize("n_steps", [1, 3])
@pytest.mark.parametrize("ema_mode", ["post", "reference_pre"])
@pytest.mark.parametrize("layout,tree", CASES)
def test_update_matches_the_reference(mesh8, layout, tree, ema_mode,
                                      n_steps):
    """Parameters, momentum (it carries over steps) and EMA target after
    1 and 3 updates; counters tick once per update; BatchNorm statistics
    pass through."""
    arm = Update(tree, layout, mesh8, scfg=_scfg(ema_update_mode=ema_mode))
    state, metrics = arm.run(n_steps)
    want = reference(arm.params0, arm.target0,
                     [arm.grads(k) for k in range(n_steps)],
                     ema_pre=ema_mode == "reference_pre")
    _close(state.params, want["params"])
    trace, count = extract_sgdm_state(state.opt_state)
    _close(trace, want["momentum"])
    _close(state.target_params, want["target"])
    assert int(count) == int(state.step) == int(state.ema_step) == n_steps
    assert state.batch_stats == {} and state.polyak_params is None
    assert set(metrics) == {"loss_mean"}


@pytest.mark.parametrize("layout,tree,wd", [c + (WD,) for c in CASES] + [
    ("replicated", "mixed", 0.0), ("zero1", "mixed", 0.0)])
def test_telemetry_reports_the_ratio_applied(mesh8, layout, tree, wd):
    """The trust spread in the health vector is the reference's applied
    ratios — of the gradient AFTER the weight-decay fold-in, identity for
    a tree with nothing adapted; the norms are of the unpadded trees in
    either layout."""
    arm = Update(tree, layout, mesh8,
                 scfg=_scfg(telemetry="step", weight_decay=wd))
    state, metrics = arm.run(2)
    grads = [arm.grads(k) for k in range(2)]
    want = reference(arm.params0, arm.target0, grads, ema_pre=False, wd=wd)
    got = health.unpack(metrics["health"])
    r = want["ratios"]
    np.testing.assert_allclose(
        [got["trust_min"], got["trust_median"], got["trust_max"]],
        [r.min(), np.median(r), r.max()], rtol=2e-5)
    if tree == "all_1d":
        assert got["trust_min"] == got["trust_max"] == 1.0
    drift = jax.tree_util.tree_map(np.subtract, want["params"],
                                   want["target"])
    np.testing.assert_allclose(
        [got["grad_norm"], got["update_norm"], got["param_norm"],
         got["ema_drift"], got["ema_drift_rel"]],
        [_norm(grads[-1]), _norm(want["update"]), _norm(want["params"]),
         _norm(drift), _norm(drift) / _norm(want["params"])], rtol=2e-5)
    assert got["collapse_feature_std"] == 0.25
    assert got["collapse_cosine_mean"] == 0.5
    assert got["loss"] == 1.5 and got["nonfinite_count"] == 0.0
    assert set(metrics) == {"loss_mean", "health"}


@pytest.mark.parametrize("tree", ["mixed", "all_1d"])
def test_zero1_health_vector_equals_replicated(mesh8, tree):
    """Read in the flat sharded layout, every slot equals the replicated
    step's: zero padding adds to no norm."""
    vecs = {layout: np.asarray(
        Update(tree, layout, mesh8, scfg=_scfg(telemetry="step"))
        .run(3)[1]["health"]) for layout in ("replicated", "zero1")}
    np.testing.assert_allclose(vecs["zero1"], vecs["replicated"],
                               rtol=1e-6, atol=1e-7)


def test_zero1_padding_stays_zero(mesh8):
    """Every flat leaf is its shaped leaf plus a zero tail, after updates
    too: the tail takes no weight decay, no ratio, no momentum, no EMA."""
    arm = Update("mixed", "zero1", mesh8, scfg=_scfg())
    arm.run(3)
    trace, _ = extract_sgdm_state(arm.state.opt_state)
    sizes = [int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        TREES["mixed"], is_leaf=lambda s: isinstance(s, tuple))]
    for flat_tree in (trace, arm.state.target_params):
        leaves = jax.tree_util.tree_leaves(flat_tree)
        assert len(leaves) == len(sizes)
        for leaf, size in zip(leaves, sizes):
            assert leaf.ndim == 1 and leaf.shape[0] % 8 == 0
            assert leaf.shape[0] > size          # every leaf IS padded
            assert "data" in str(leaf.sharding.spec)
            assert not np.any(np.asarray(leaf)[size:])
            assert np.any(np.asarray(leaf)[:size])


@pytest.mark.parametrize("polyak", [False, True])
@pytest.mark.parametrize("layout", ["replicated", "zero1"])
def test_polyak_average(mesh8, layout, polyak):
    """Averages the fresh (gathered) parameters when asked to and the
    state holds a tree for it; else the state's field stays None."""
    arm = Update("mixed", layout, mesh8, polyak=polyak,
                 scfg=_scfg(polyak_ema=POLYAK if polyak else 0.0))
    state, _ = arm.run(3)
    want = reference(arm.params0, arm.target0,
                     [arm.grads(k) for k in range(3)], ema_pre=False,
                     polyak=POLYAK if polyak else None)
    if polyak:
        _close(state.polyak_params, want["polyak"])
    else:
        assert state.polyak_params is None
    _close(state.params, want["params"])


@pytest.mark.parametrize("optimizer", ["lars_momentum", "lars_adam",
                                       "lars_momentum+clip", "momentum",
                                       "sgd", "adam", "lamb", "rmsprop",
                                       "adadelta"])
def test_zero1_equals_replicated_for_every_chain(mesh8, optimizer):
    """The two layouts differ in where the trees live, not in what the
    chain computes; a chain without LARS reports identity trust."""
    scfg = _scfg(telemetry="step",
                 lars_in_chain=optimizer.startswith("lars_"))
    out = {layout: Update("mixed", layout, mesh8, scfg=scfg,
                          optimizer=optimizer).run(3)
           for layout in ("replicated", "zero1")}
    (rep, rep_m), (z1, z1_m) = out["replicated"], out["zero1"]
    _close(z1.params, rep.params, rtol=1e-5)
    _close(z1.target_params, rep.target_params, rtol=1e-5)
    _close(z1.opt_state, rep.opt_state, rtol=1e-5)
    got = health.unpack(z1_m["health"])
    if not optimizer.startswith("lars_"):
        assert got["trust_min"] == got["trust_max"] == 1.0
    np.testing.assert_allclose(np.asarray(z1_m["health"]),
                               np.asarray(rep_m["health"]), rtol=1e-5,
                               atol=1e-7)


@pytest.mark.parametrize("layout", ["replicated", "zero1"])
def test_a_nonfinite_gradient_is_counted(mesh8, layout):
    """``--nan-policy`` keys off this slot of the vector."""
    arm = Update("mixed", layout, mesh8, scfg=_scfg(telemetry="step"))
    grads = arm.grads(0)
    grads["dense"]["kernel"][2, 3] = np.nan
    grads["bn"]["bias"][0] = np.inf
    assert health.unpack(arm.step(grads)["health"])["nonfinite_count"] == 2


@pytest.mark.parametrize("layout", ["replicated", "zero1"])
def test_tau_follows_the_persisted_ema_counter(mesh8, layout):
    """The EMA schedule reads ``ema_step`` (restored from a checkpoint),
    not the optimizer step."""
    arm = Update("mixed", layout, mesh8, scfg=_scfg())
    arm.state = arm.state.replace(ema_step=jnp.asarray(5, jnp.int32))
    state, _ = arm.run(2)
    want = reference(arm.params0, arm.target0,
                     [arm.grads(k) for k in range(2)], ema_pre=False,
                     ema_step0=5)
    _close(state.target_params, want["target"])
    assert int(state.ema_step) == 7 and int(state.step) == 2


@pytest.mark.parametrize("layer_scopes", [(), ("mla", "moe/experts")])
def test_scope_names_are_stamped_as_an_attribute(mesh8, layer_scopes):
    """The phases' names (and a backbone's own) ride one instruction as a
    real attribute, so a rename changes the compilation-cache key."""
    arm = Update("all_1d", "replicated", mesh8, scfg=_scfg(),
                 layer_scopes=layer_scopes)
    text = arm.fn.lower(arm.state, arm.grads(0), {},
                        arm.metrics_in()).as_text()
    stamp = " ".join(steps.PHASE_SCOPES + layer_scopes)
    assert f'phase_scopes = "{stamp}"' in text


# ---------------------------------------------------------------------------
# the fork that went (PR 29): three flags, three fields, one state field,
# two builder parameters — refused, not ignored
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flag,value", [("--fused-update", "on"),
                                        ("--flat-resident", "on"),
                                        ("--flat-bucket-mb", "64")])
def test_the_parser_refuses_a_removed_flag(flag, value, capsys):
    from byol_tpu.cli import build_parser
    with pytest.raises(SystemExit) as e:
        build_parser().parse_args([flag, value])
    assert e.value.code == 2
    assert f"unrecognized arguments: {flag}" in capsys.readouterr().err


@pytest.mark.parametrize("group,field", [("OptimConfig", "fused_update"),
                                         ("DeviceConfig", "flat_resident"),
                                         ("DeviceConfig", "flat_bucket_mb")])
def test_the_config_has_no_removed_field(group, field):
    cls = getattr(config_lib, group)
    assert field not in {f.name for f in dataclasses.fields(cls)}
    with pytest.raises(TypeError):
        cls(**{field: "on"})


def test_one_update_path_in_the_builders():
    assert "flat_ctx" not in inspect.signature(
        steps.make_train_step).parameters
    assert list(inspect.signature(steps.make_eval_step).parameters) == [
        "net", "scfg", "policy", "zero1_ctx"]
    assert list(inspect.signature(build_plan).parameters) == ["mesh",
                                                               "zero1"]
    assert not {"fused_update", "flat_resident", "clip"} & {
        f.name for f in dataclasses.fields(steps.StepConfig)}
    assert "flat_shadow" not in {
        f.name for f in dataclasses.fields(TrainState)}
    assert inspect.getmodule(steps.apply_update) is steps


def test_a_run_header_with_the_old_plan_keys_still_validates(mesh8):
    """Run logs written before PR 29 carry ``flat_resident`` /
    ``flat_bucket_mb`` in ``sharding_plan``; they stay readable, and the
    plan no longer writes the two keys."""
    plan = build_plan(mesh8).describe()
    assert not {"flat_resident", "flat_bucket_mb"} & set(plan)
    header = {"v": events.SCHEMA_VERSION, "kind": "run_header", "t": 0.0,
              "sharding_plan": dict(plan, flat_resident="on",
                                    flat_bucket_mb=64)}
    header.update({f: "x" for f in events.EVENT_KINDS["run_header"]})
    assert events.validate_event(header) is header
