"""The expert layer's routing without a sort (``ops/expert_routing.py``): the
kernels, under the Pallas interpreter, against the ``jax.numpy`` body they
replace on a TPU — ``top_k`` and the two ``argsort`` — table for table and
through the whole layer.  A case is a trunk's ``(E, k, held, lo, scoring)``
at 1,024 tokens, or an edge of the tables."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byol_tpu.models import decoder_trunk as trunk_lib
from byol_tpu.ops import expert_routing

TOKENS = 1024


@dataclasses.dataclass(frozen=True)
class Case:
    experts: int
    k: int
    held: int
    lo: int
    scoring: str = "softmax"
    scaling: float = 1.0
    # how the router's rows are made: 'normal'; 'ties' (few distinct values
    # a row: the first index must win); 'crowd' (every token's best experts
    # are the held ones: a load far past the usual window)
    rows: str = "normal"
    tokens: int = TOKENS


CASES = {
    # the six trunk cells' routers
    "xing4": Case(64, 4, 8, 8, "sigmoid", 2.0),
    "qwen3next": Case(512, 10, 32, 64),
    "keye_sdar": Case(128, 8, 16, 0),
    "lfm2": Case(64, 4, 8, 56, "sigmoid"),
    "joyai": Case(256, 8, 16, 16, "sigmoid", 2.5),
    # the edges
    "ties_softmax": Case(128, 8, 16, 16, rows="ties"),
    "ties_sigmoid": Case(64, 4, 8, 0, "sigmoid", rows="ties"),
    "no_copy_held": Case(64, 2, 2, 62, rows="crowd"),
    "every_copy_held": Case(64, 4, 64, 0),
    "one_bucket_holds_all": Case(64, 1, 1, 0, rows="crowd"),
    "more_slots_than_held": Case(64, 6, 4, 4, rows="crowd"),
    "past_usual": Case(128, 8, 16, 0, "sigmoid", rows="crowd"),
    "two_row_tiles": Case(64, 4, 8, 8, tokens=2 * TOKENS),
}


def _router_rows(case: Case, seed: int = 0):
    """``select, values``: what the layer hands :func:`choose`."""
    key, other = jax.random.split(jax.random.PRNGKey(seed))
    logits = jax.random.normal(key, (case.tokens, case.experts), jnp.float32)
    if case.rows == "ties":
        logits = jnp.round(logits)              # a handful of values a row
    if case.rows == "crowd":                    # experts 0.. are everyone's
        logits = logits - 8.0 * jnp.arange(case.experts)
    if case.scoring == "softmax":
        return jax.nn.softmax(logits, axis=-1), None
    scores = jax.nn.sigmoid(logits)
    bias = 0.05 * jax.random.normal(other, (case.experts,), jnp.float32)
    if case.rows == "ties":
        bias = jnp.zeros_like(bias)
    return scores + bias, scores


@pytest.mark.parametrize("name", sorted(CASES))
def test_tables_equal_the_sorted_ones(name):
    """``chosen``, ``weight``, ``group_sizes`` equal everywhere; ``place`` on
    every held copy, ``token_of`` / ``weight_of`` on every row below
    ``rows_held`` (past it: a token in bounds, a weight of 0); the gradient
    of a weighted sum of ``weight_of`` the same to the bit."""
    case = CASES[name]
    select, values = _router_rows(case)
    mix = jnp.cos(0.37 * jnp.arange(case.tokens * case.k))

    @jax.jit
    def both(select, values):
        def route(kernel):
            def tables(select, values):
                weight, chosen = expert_routing.choose(
                    select, values, case.k, kernel=kernel)
                got = expert_routing.tables(chosen, 1.5 * weight, case.lo,
                                            case.held, kernel=kernel)
                return jnp.sum(got.weight_of * mix), (weight, chosen, got)
            grad, out = jax.grad(tables, argnums=0 if values is None else 1,
                                 has_aux=True)(select, values)
            return out + (grad,)
        return route(False), route(True)

    want, got = jax.device_get(both(select, values))
    np.testing.assert_array_equal(got[1], want[1])            # chosen
    np.testing.assert_array_equal(got[0], want[0])            # weight
    if case.rows == "ties":     # the oracle itself: a tie to the lower index
        rows = np.asarray(select)
        order = np.argsort(-rows, axis=-1, kind="stable")[:, :case.k]
        np.testing.assert_array_equal(got[1], order)
    local = want[1] - case.lo
    here = (local >= 0) & (local < case.held)
    np.testing.assert_array_equal(got[2].group_sizes, want[2].group_sizes)
    assert got[2].group_sizes.sum() == here.sum()
    rows_held = int(here.sum())
    np.testing.assert_array_equal(got[2].place[here], want[2].place[here])
    np.testing.assert_array_equal(got[2].token_of[:rows_held],
                                  want[2].token_of[:rows_held])
    np.testing.assert_array_equal(got[2].weight_of[:rows_held],
                                  want[2].weight_of[:rows_held])
    assert not got[2].weight_of[rows_held:].any()
    assert got[2].token_of.min() >= 0
    assert got[2].token_of.max() < case.tokens
    assert got[2].place.min() >= 0
    assert got[2].place.max() < case.tokens * case.k
    np.testing.assert_array_equal(got[3], want[3])            # the gradient
    if name == "no_copy_held":
        assert rows_held == 0
    if name in ("every_copy_held", "one_bucket_holds_all"):
        assert rows_held == case.tokens * case.k


def _sizes(case: Case) -> trunk_lib.TrunkSizes:
    return dataclasses.replace(
        trunk_lib.TINY, hidden_size=128, n_routed_experts=case.experts,
        moe_intermediate_size=128, num_experts_per_tok=case.k,
        n_shared_experts=0, routed_scaling_factor=case.scaling,
        scoring_func=case.scoring)


@pytest.mark.parametrize("name,whole_bytes,branch", [
    ("xing4", None, "usual"), ("qwen3next", None, "usual"),
    ("joyai", None, "usual"), ("lfm2", None, "usual"),
    ("keye_sdar", None, "usual"),
    ("past_usual", None, "product(every)"),
    ("past_usual", 1, "in_slabs"),
    ("more_slots_than_held", 1, "in_slabs"),
    ("every_copy_held", None, "every")])
def test_expert_layer_equals_the_sorting_one(monkeypatch, name, whole_bytes,
                                             branch):
    """The layer with the kernels against the layer with the ``jax.numpy``
    body: the output and the gradients of the router, the inputs and the
    experts within float32 rounding of one sum; the selection
    bias takes none; the counters the same.  A load past ``usual`` takes the
    fallback — one product over every copy, or slabs — with every row
    computed."""
    case = CASES[name]
    z = _sizes(case)
    if whole_bytes is not None:
        monkeypatch.setattr(trunk_lib, "WHOLE_FALLBACK_BYTES", whole_bytes)
    layer = trunk_lib.ExpertLayer(z, case.lo, case.held, jnp.float32)
    key, other = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(key, (2, case.tokens // 2, z.hidden_size),
                          jnp.float32)
    params = layer.init(other, x[:, :8])["params"]
    if case.rows == "crowd":        # the held experts are everyone's best
        x = x.at[..., 0].set(4.0)
        held = ((jnp.arange(case.experts) >= case.lo)
                & (jnp.arange(case.experts) < case.lo + case.held))
        params = dict(params, router=params["router"].at[0].set(
            jnp.where(held, 2.0, 0.0)))

    def run(kernel):
        monkeypatch.setattr(expert_routing, "applies",
                            lambda *a, **k: kernel)

        def loss(params, x):
            out, sown = layer.apply({"params": params}, x,
                                    mutable=[trunk_lib.ROUTING])
            return jnp.sum(jnp.square(out)), (
                out, sown[trunk_lib.ROUTING]["stats"][0])
        (_, (out, stats)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(params, x)
        return jax.device_get((out, stats, grads))

    want, got = run(False), run(True)
    np.testing.assert_array_equal(got[1], want[1])
    rows_held, _, _, dropped = want[1]
    every = case.tokens * case.k
    usual = min(every, -(-2 * every * case.held // case.experts))
    assert dropped == 0
    assert (rows_held > usual) == (branch in ("product(every)", "in_slabs"))
    assert (usual == every) == (branch == "every")
    close = lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5,
                                                   atol=2e-5 * np.abs(b).max())
    close(got[0], want[0])
    jax.tree_util.tree_map(close, got[2], want[2])
    if case.scoring == "sigmoid":
        assert not np.asarray(
            got[2][0]["e_score_correction_bias"]).any()
    assert np.abs(want[2][0]["router"]).max() > 0
