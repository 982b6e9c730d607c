"""The phases of the train step are named in the compiled program.

``training/steps.py`` wraps the target forward, the online forward, the loss,
the update and the in-step augmentation in ``jax.named_scope``; every HLO
instruction carries its scope path as ``op_name``, and the benchmark splits a
device trace by those names (PERF.md section 3).  Pinned here, on the CPU at
tiny size, from ``compiled.as_text()``:

- every phase token the configuration traces occurs, inside the accumulation
  scan too;
- ``target_forward`` never occurs under ``transpose(`` (the target network
  has no backward);
- under a tenth of the traced instructions carry no phase;
- the step carries the scope names as a REAL attribute of one instruction,
  because the persistent compilation cache keys a program with its debug
  info stripped: without it a rename of a scope is served the executable
  cached before the rename;
- every ``pallas_call`` carries its name.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.core import config as config_lib
from byol_tpu.parallel.compile_plan import build_plan
from byol_tpu.parallel.mesh import MeshSpec, build_mesh, shard_batch_to_mesh
from byol_tpu.training import steps as steps_lib
from byol_tpu.training.build import setup_training

BATCH, IMAGE, RAW = 8, 32, 40
PHASES = ("augment", "target_forward", "online_forward", "loss", "update")

CASES = {
    "resnet18": dict(arch="resnet18"),
    "vit_s16": dict(arch="vit_s16"),
    "resnet18_accum2": dict(arch="resnet18", accum=2),
    "resnet18_augment_in_step": dict(arch="resnet18", placement="step"),
}


def _compiled_text(arch, accum=1, placement="loader"):
    c = config_lib.Config()
    c = c.replace(
        task=dataclasses.replace(c.task, batch_size=BATCH, epochs=2,
                                 image_size_override=IMAGE,
                                 augment_placement=placement),
        model=dataclasses.replace(c.model, arch=arch, head_latent_size=32,
                                  projection_size=16),
        optim=dataclasses.replace(c.optim, warmup=1, accum_steps=accum),
        device=dataclasses.replace(c.device, num_replicas=1, half=False))
    rcfg = config_lib.resolve(
        c, num_train_samples=64, num_test_samples=BATCH, output_size=10,
        input_shape=(IMAGE, IMAGE, 3))
    mesh = build_mesh(MeshSpec(data=1), jax.devices()[:1])
    _, state, step, _, _ = setup_training(
        rcfg, mesh, jax.random.PRNGKey(0), plan=build_plan(mesh))
    label = np.zeros((BATCH,), np.int32)
    if placement == "step":
        batch = {"images": np.zeros((BATCH, RAW, RAW, 3), np.uint8),
                 "label": label}
    else:
        view = np.zeros((BATCH, IMAGE, IMAGE, 3), np.float32)
        batch = {"view1": view, "view2": view, "label": label}
    with mesh:
        return step.__wrapped__.lower(
            state, shard_batch_to_mesh(batch, mesh)).compile().as_text()


@pytest.fixture(scope="module")
def compiled():
    """``case -> compiled.as_text()``, compiled once.  The persistent cache
    keys a program WITHOUT its debug info, so it would hand these tests the
    names of whatever step was cached first; for this module the metadata
    is part of the key."""
    texts = {}
    option = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, option)
    jax.config.update(option, True)

    def get(case):
        if case not in texts:
            texts[case] = _compiled_text(**CASES[case])
        return texts[case]
    yield get
    jax.config.update(option, before)


def _traced_op_names(text):
    """``op_name`` of every instruction the step traced (``jit(...)/...``),
    parameters and constants left out: reducer bodies and arguments carry
    bare names."""
    names = []
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if not m or not m.group(1).startswith("jit("):
            continue
        if re.search(r"= \S+ (parameter|constant)\(", line):
            continue
        names.append(m.group(1))
    return names


@pytest.mark.parametrize("case", CASES)
def test_every_phase_of_the_configuration_is_named(compiled, case):
    names = _traced_op_names(compiled(case))
    expected = set(PHASES)
    if CASES[case].get("placement", "loader") == "loader":
        expected.discard("augment")
        assert not any("augment" in n for n in names)
    for phase in expected:
        assert any(f"/{phase}/" in n or f"({phase})" in n for n in names), \
            phase
    # the backward is the transpose of the differentiated phases
    assert any("transpose(jvp(online_forward))" in n for n in names)
    if CASES[case].get("accum", 1) > 1:
        # inside the scan too; there ``update`` is the running sums
        for phase in ("target_forward", "online_forward", "update"):
            assert any(phase in n and "while/body" in n for n in names), \
                phase


@pytest.mark.parametrize("case", CASES)
def test_the_target_network_has_no_backward(compiled, case):
    for name in _traced_op_names(compiled(case)):
        if "transpose(" in name:
            assert "target_forward" not in name, name


@pytest.mark.parametrize("case", CASES)
def test_under_a_tenth_of_the_instructions_carry_no_phase(compiled, case):
    names = _traced_op_names(compiled(case))
    bare = [n for n in names if not any(p in n for p in PHASES)]
    assert len(names) > 500
    assert len(bare) < 0.1 * len(names), sorted(set(bare))[:20]


def test_scope_names_are_the_contract_and_a_real_attribute(compiled):
    assert steps_lib.PHASE_SCOPES == PHASES
    stamped = [ln for ln in compiled("resnet18").splitlines()
               if "frontend_attributes={" in ln and "phase_scopes=" in ln]
    assert stamped and all(
        f'phase_scopes="{" ".join(PHASES)}"' in ln for ln in stamped)
    # one scalar add (and the fusion XLA wraps it in), nothing else
    assert all(re.search(r"= s32\[\] (add|fusion)\(", ln) for ln in stamped)


def test_a_scope_outside_the_contract_is_refused():
    with pytest.raises(ValueError):
        steps_lib._phase("forward")


def _pallas_names(fn, *args):
    """``name`` of every ``pallas_call`` in the jaxpr of ``fn(*args)``."""
    def walk(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["name"])
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    if hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr, out)
                    elif hasattr(sub, "eqns"):
                        walk(sub, out)
        return out
    return walk(jax.make_jaxpr(fn)(*args).jaxpr, [])


def _two_view():
    from byol_tpu.ops import fused_two_view
    images = jnp.zeros((2, RAW, RAW, 3), jnp.uint8)
    return _pallas_names(
        lambda im: fused_two_view(jax.random.PRNGKey(0), im, IMAGE,
                                  interpret=True), images)


def _packed_attention():
    from byol_tpu.ops import packed_self_attention
    qkv = jnp.ones((2, 8, 3 * 128), jnp.float32)
    return _pallas_names(jax.grad(lambda x: jnp.sum(
        packed_self_attention(x, 2, interpret=True))), qkv)


def _tiled_causal_attention(selected):
    """The one kernel pair of ops/causal_attention.py: named after whether
    the call has a selection."""
    from byol_tpu.ops import causal_attention
    q, kv = jnp.ones((1, 1, 2, 16, 8)), jnp.ones((1, 1, 16, 8))
    keep = jnp.ones((3, 1, 8, 8), bool) if selected else None
    return _pallas_names(jax.grad(lambda q: jnp.sum(causal_attention.attend(
        q, kv, kv, scale=1.0, block=8, selected=keep,
        interpret=True)[0])), q)


@pytest.mark.parametrize("entry,expected", [
    (_two_view, ["fused_two_view"]),
    (_packed_attention, ["packed_attention_fwd", "packed_attention_bwd"]),
    (lambda: _tiled_causal_attention(False),
     ["causal_attention_fwd", "causal_attention_bwd"]),
    (lambda: _tiled_causal_attention(True),
     ["selected_attention_fwd", "selected_attention_bwd"]),
], ids=["fused_two_view", "packed_self_attention", "causal_attention",
        "selected_attention"])
def test_each_pallas_call_carries_its_name(entry, expected):
    assert entry() == expected
