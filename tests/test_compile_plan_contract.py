"""Runtime witness for GL112's static claim (ISSUE 17 satellite).

graphlint's GL112 diffs jit wiring against the compile plan's declared
``DONATE`` data *syntactically*; this module diffs the SAME declaration
against what XLA actually compiled, so the contract is pinned from both
sides: if a builder ever donates or places something ``describe()`` does
not declare, either GL112 (source) or this test (compiled artifact)
breaks.

What the compiled executable exposes (jax 0.4.x, CPU backend included):

- donation surfaces as an ``input_output_alias`` table in
  ``compiled.as_text()`` (and per-arg ``tf.aliasing_output`` attributes
  in the lowered StableHLO) — present iff the entry point donates;
- placement surfaces as ``compiled.input_shardings`` /
  ``compiled.output_shardings`` NamedShardings, which must match the
  plan's ``batch_sharding`` / ``replicated`` properties.

Trivial step bodies stand in for the real ones — donation and sharding
are properties of the jit WRAPPER (the plan's builders), not of the
wrapped computation, and tiny bodies keep the five compiles cheap.
"""
import warnings

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from byol_tpu.parallel.compile_plan import (DONATE, build_plan,
                                            jit_encoder_extractor)

BATCH = 16      # divisible by the 8-way data axis


def _state():
    return {"w": jnp.ones((4, 4)), "m": jnp.zeros((4, 4))}


def _batch():
    return jnp.ones((BATCH, 8))


def _train_fn(state, batch):
    w = state["w"] + jnp.sum(batch)
    return {"w": w, "m": state["m"] * 0.9}, jnp.mean(batch)


def _eval_fn(state, batch):
    return jnp.mean(state["w"]) + jnp.mean(batch)


def _extract_fn(x, y, mask):
    return x * 2.0, y, mask


def _serve_fn(x):
    return x @ jnp.ones((8, 4))


def _compiled(jitted, *args):
    return jitted.lower(*args).compile()


def _aliases(compiled) -> bool:
    return "input_output_alias" in compiled.as_text()


def _flat_input_shardings(compiled):
    return jax.tree_util.tree_leaves(compiled.input_shardings)


class TestDescribeMatchesDonate:
    def test_describe_reports_every_entry(self, mesh8):
        plan = build_plan(mesh8)
        desc = plan.describe()
        assert desc["donate_argnums"] == {
            k: list(v) for k, v in DONATE.items()}

    def test_every_entry_has_a_builder(self, mesh8):
        """A DONATE key without a jit_<entry> builder is dead wiring —
        the runtime face of GL112-unused-entry."""
        plan = build_plan(mesh8)
        for entry in DONATE:
            if entry == "encoder_extractor":
                assert callable(jit_encoder_extractor)
            else:
                assert callable(getattr(plan, f"jit_{entry}")), entry


class TestCompiledDonationMatchesPlan:
    """For each entry point: the compiled executable carries an
    input_output_alias table IFF the plan declares a donation."""

    def _compiled_for(self, plan, entry):
        state = _state()
        state_sh = plan.state_sharding(state)
        if entry == "train_step":
            return _compiled(plan.jit_train_step(_train_fn, state_sh),
                             state, _batch())
        if entry == "eval_step":
            return _compiled(plan.jit_eval_step(_eval_fn, state_sh),
                             state, _batch())
        if entry == "spmd_extractor":
            return _compiled(plan.jit_spmd_extractor(_extract_fn),
                             _batch(), jnp.ones((BATCH,)),
                             jnp.ones((BATCH,)))
        if entry == "serve_step":
            return _compiled(plan.jit_serve_step(_serve_fn), _batch())
        assert entry == "encoder_extractor"
        return _compiled(jit_encoder_extractor(_serve_fn), _batch())

    @pytest.mark.parametrize("entry", sorted(DONATE))
    def test_alias_table_iff_donation_declared(self, mesh8, entry):
        """Declared donation leaves a compiled trace either way XLA takes
        it: an input_output_alias table when the buffer is reusable
        (train_step: state leaves alias same-shaped outputs), or the
        "donated buffers were not usable" warning when the geometry
        forbids aliasing (serve_step here: a data-sharded input cannot
        alias a replicated output on this toy shape — the donation still
        frees the staging buffer's HBM early on TPU).  An entry declared
        non-donating must produce NEITHER."""
        plan = build_plan(mesh8)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            compiled = self._compiled_for(plan, entry)
        dropped = any("donated buffers were not usable"
                      in str(w.message).lower() for w in caught)
        donated = _aliases(compiled) or dropped
        declared = bool(DONATE[entry])
        assert donated == declared, (
            f"{entry}: plan declares donate={DONATE[entry]} but the "
            f"compiled executable says aliasing={_aliases(compiled)}, "
            f"dropped-donation-warning={dropped}")

    def test_train_step_aliases_the_state_argument(self, mesh8):
        """Not just *some* alias: the donated argnum 0 is the state —
        every state leaf input must be aliased to an output."""
        plan = build_plan(mesh8)
        state = _state()
        jitted = plan.jit_train_step(_train_fn, plan.state_sharding(state))
        lowered_text = jitted.lower(state, _batch()).as_text()
        n_state_leaves = len(jax.tree_util.tree_leaves(state))
        assert lowered_text.count("tf.aliasing_output") == n_state_leaves


class TestCompiledShardingsMatchPlan:
    def test_train_step_batch_over_data_metrics_replicated(self, mesh8):
        plan = build_plan(mesh8)
        state = _state()
        state_sh = plan.state_sharding(state)
        compiled = _compiled(plan.jit_train_step(_train_fn, state_sh),
                             state, _batch())
        in_sh = _flat_input_shardings(compiled)
        # last input leaf is the batch: sharded over the data axis
        assert in_sh[-1].is_equivalent_to(plan.batch_sharding, 2), (
            in_sh[-1])
        # metrics output (last leaf) comes back replicated
        out_sh = jax.tree_util.tree_leaves(compiled.output_shardings)
        assert out_sh[-1].is_equivalent_to(plan.replicated, 0), out_sh[-1]

    def test_serve_step_input_sharded_output_replicated(self, mesh8):
        plan = build_plan(mesh8)
        compiled = _compiled(plan.jit_serve_step(_serve_fn), _batch())
        (in_sh,) = _flat_input_shardings(compiled)
        assert in_sh.is_equivalent_to(plan.batch_sharding, 2), in_sh
        (out_sh,) = jax.tree_util.tree_leaves(compiled.output_shardings)
        assert out_sh.is_equivalent_to(plan.replicated, 2), out_sh

    def test_spmd_extractor_outputs_all_replicated(self, mesh8):
        """The replicated out_shardings IS the cross-host all-gather of
        the linear-eval extraction — all three outputs replicated."""
        plan = build_plan(mesh8)
        compiled = _compiled(plan.jit_spmd_extractor(_extract_fn),
                             _batch(), jnp.ones((BATCH,)),
                             jnp.ones((BATCH,)))
        for sh in jax.tree_util.tree_leaves(compiled.output_shardings):
            assert sh.spec == P() or all(a is None for a in sh.spec), sh

    def test_batch_sharding_is_data_axis(self, mesh8):
        plan = build_plan(mesh8)
        assert plan.batch_sharding.spec == P("data")
        assert plan.replicated.spec == P()
