"""``ops/moe.py held_experts``: the buffer the sorted assignments go through
is as long as the share of the experts held here asks for, and what comes
out is, bit for bit, what the full-length layer of PR 28 gave."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_granite_hybrid import TINY as GRANITE_TINY
from video_features_tpu.models import granite_hybrid as gh
from video_features_tpu.ops import moe

D, INNER, WIDE, K = 32, 16, 8, 4
T = 1024                # 4,096 assignments: half held -> a buffer of 3,072


def full_length(u, gates, experts, w_in, w_out, first, valid):
    """PR 28's ``held_experts``, kept as the reference: every step runs over
    all ``T * K`` assignments."""
    t, k = experts.shape
    held = w_in.shape[0]
    local = experts - first
    here = (local >= 0) & (local < held) & valid[:, None]
    group = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(group, stable=True)
    sizes = (group[:, None] == jnp.arange(held)).sum(axis=0, dtype=jnp.int32)
    rows = u[order // k]
    hidden = jax.lax.ragged_dot(rows, w_in, sizes,
                                preferred_element_type=u.dtype)
    gate, up = jnp.split(hidden, 2, axis=-1)
    out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_out, sizes,
                             preferred_element_type=u.dtype)
    place = jnp.argsort(order)
    picked = jnp.where(here[..., None], out[place].reshape(t, k, -1), 0)
    weight = jnp.where(here, gates, 0.0)
    return jnp.sum(weight[..., None] * picked.astype(jnp.float32), axis=1)


def layer_inputs(seed, dtype, held, first=0, skew=0.0, padding=0):
    """``(u, gates, experts, w_in, w_out, first, valid)``. Every token
    chooses ``K`` different experts of ``WIDE``; ``skew`` is the share of
    tokens whose choices are all among the first ``K`` experts."""
    rng = np.random.default_rng(seed)
    experts = np.argsort(rng.random((T, WIDE)), axis=1)[:, :K]
    crowded = rng.random(T) < skew
    experts[crowded] = np.argsort(rng.random((T, K)), axis=1)[crowded]
    gates = jax.nn.softmax(jnp.asarray(rng.standard_normal((T, K)),
                                       jnp.float32), axis=-1)
    w_in = rng.standard_normal((held, D, 2 * INNER)) / np.sqrt(D)
    w_out = rng.standard_normal((held, INNER, D)) / np.sqrt(INNER)
    valid = np.arange(T) < T - padding
    return (jnp.asarray(rng.standard_normal((T, D)), dtype), gates,
            jnp.asarray(experts, jnp.int32), jnp.asarray(w_in, dtype),
            jnp.asarray(w_out, dtype), first, jnp.asarray(valid))


#: name -> (layer_inputs' keywords, whether the held count fits the buffer)
CASES = {
    "every_expert_held": (dict(held=WIDE), True),
    "half_held_balanced": (dict(held=WIDE // 2), True),
    "half_held_skewed_past_the_buffer": (dict(held=WIDE // 2, skew=0.9),
                                         False),
    "the_second_chips_share": (dict(held=WIDE // 2, first=WIDE // 2), True),
    "padding_rows": (dict(held=WIDE // 2, padding=100), True),
    "a_token_with_no_held_choice": (dict(held=WIDE // 2, first=WIDE // 2,
                                         skew=0.3), True),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_held_experts_gives_the_bits_of_the_full_length_layer(case, dtype):
    keywords, fits = CASES[case]
    u, gates, experts, w_in, w_out, first, valid = layer_inputs(
        len(case), dtype, **keywords)
    held = w_in.shape[0]
    local = np.asarray(experts) - first
    here = (local >= 0) & (local < held) & np.asarray(valid)[:, None]
    n = moe.held_rows(T * K, held, WIDE)
    # the case is what its name says
    assert n == (T * K if held == WIDE else 3072)
    assert (here.sum() <= n) == fits
    if case == "a_token_with_no_held_choice":
        assert (~here.any(axis=1) & np.asarray(valid)).sum() > 100
    want = jax.jit(full_length, static_argnums=5)(
        u, gates, experts, w_in, w_out, first, valid)
    got = jax.jit(moe.held_experts, static_argnums=(5, 7))(
        u, gates, experts, w_in, w_out, first, valid, WIDE)
    assert got.dtype == jnp.float32 and got.shape == (T, D)
    assert np.asarray(want)[here.any(axis=1)].any()
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("assignments,held,wide,rows", [
    (163840, 36, 72, 102400),       # the benchmark's cell
    (163840, 72, 72, 163840),       # layer_shards: 1
    (163840, 18, 72, 51200), (163840, 9, 72, 25600),
    (4096, 4, 8, 3072),             # up to a multiple of 1,024
    (576, 4, 8, 576),               # a buffer never longer than all
])
def test_the_buffer_follows_the_held_share(assignments, held, wide, rows):
    assert moe.held_rows(assignments, held, wide) == rows


# -- what the program holds: read from the jaxpr ------------------------------------

def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) \
                    else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from equations(inner)


def shaped_equations(jaxpr):
    """``primitive: input shapes -> output shapes`` of every equation,
    sorted: what a program computes, whatever order it was written in."""
    return sorted(
        f"{e.primitive.name}: "
        f"{[str(getattr(v, 'aval', v)) for v in e.invars]} -> "
        f"{[str(v.aval) for v in e.outvars]}" for e in equations(jaxpr))


def row_buffers(jaxpr, width):
    """The leading lengths of what each ``ragged_dot`` reads and writes and
    of every gather of a (rows, ``width``) array."""
    lengths = {"ragged_dot": [], "gather": []}
    for eqn in equations(jaxpr):
        name = eqn.primitive.name
        if name == "ragged_dot_general":
            lengths["ragged_dot"] += [eqn.invars[0].aval.shape[0],
                                      eqn.outvars[0].aval.shape[0]]
        elif name == "gather" and eqn.outvars[0].aval.shape[1:] == (width,):
            lengths["gather"].append(eqn.outvars[0].aval.shape[0])
    return lengths


#: two layers of the token family's tiny architecture: 8 experts, top 3
TINY = dict(GRANITE_TINY, num_hidden_layers=2)
BATCH, ROW = 16, 96     # 1,536 tokens, 4,608 assignments a layer


def step_jaxpr(shards):
    arch = gh.arch_from_config(TINY, shards, 0)
    params = jax.eval_shape(lambda: gh.init_params(arch, 0, jnp.float32))
    rows = jax.ShapeDtypeStruct((BATCH, 2, ROW), jnp.int32)
    return jax.make_jaxpr(
        lambda p, r: gh.token_states(arch, p, r, jnp.float32))(params, rows)


def test_with_half_the_experts_held_the_steps_buffers_are_the_bound_long():
    jaxpr = step_jaxpr(2).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 2                       # one a layer
    n, every = moe.held_rows(BATCH * ROW * 3, 4, 8), BATCH * ROW * 3
    assert (n, every) == (3072, 4608)
    for cond in conds:
        # index 1 is the branch taken when the held count fits
        full, compact = (row_buffers(b.jaxpr, 64)
                         for b in cond.params["branches"])
        assert compact == {"ragged_dot": [n] * 4, "gather": [n, every]}
        assert full == {"ragged_dot": [every] * 4, "gather": [every] * 2}
    # nothing of the layer's row traffic is left outside the condition
    outside = [e.primitive.name for e in jaxpr.eqns]
    assert "ragged_dot" not in outside


def test_with_every_expert_held_the_step_is_the_parents():
    jaxpr = step_jaxpr(1).jaxpr
    assert not any(e.primitive.name in ("cond", "while")
                   for e in equations(jaxpr))
    every = BATCH * ROW * 3
    found = row_buffers(jaxpr, 64)
    assert found["ragged_dot"] == [every] * 8    # two layers
    assert found["gather"] == [every] * 4
    # the layer alone: the full-length function's equations, each with the
    # shapes it has there (the inverse permutation is taken a few lines
    # earlier, nothing else differs)
    args = layer_inputs(0, jnp.bfloat16, held=WIDE)
    today = jax.make_jaxpr(lambda *a: moe.held_experts(*a, 0, args[6], WIDE)
                           )(*args[:5])
    parent = jax.make_jaxpr(lambda *a: full_length(*a, 0, args[6])
                            )(*args[:5])
    assert shaped_equations(today.jaxpr) == shaped_equations(parent.jaxpr)


# -- the gate's rule ------------------------------------------------------------------

def plain_gate(logits, k, over_all, renormalise, scaling):
    """The two published rules in numpy, token by token."""
    gates, experts = [], []
    for row in np.asarray(logits, np.float64):
        if over_all:
            p = np.exp(row - row.max())
            p /= p.sum()
            top = np.argsort(-p, kind="stable")[:k]
            g = p[top] / (p[top].sum() + 1e-20) if renormalise else p[top]
        else:
            top = np.argsort(-row, kind="stable")[:k]
            g = np.exp(row[top] - row[top].max())
            g /= g.sum()
        gates.append(g * scaling)
        experts.append(top)
    return np.asarray(gates), np.asarray(experts)


@pytest.mark.parametrize("rule", [
    dict(),                                                 # granite's
    dict(over_all=True, renormalise=False),                 # deepseek_v2's
    dict(over_all=True, renormalise=True),
    dict(over_all=True, renormalise=False, scaling=2.5),
], ids=["top_k_then_softmax", "softmax_then_top_k", "renormalised",
        "scaled"])
def test_route_under_each_rule_is_the_plain_computation(rule):
    rng = np.random.default_rng(11)
    u = jnp.asarray(rng.standard_normal((64, D)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((D, WIDE)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        gates, experts = moe.route(u, router, K, **rule)
        logits = jnp.dot(u, router)
    want_gates, want_experts = plain_gate(
        logits, K, rule.get("over_all", False),
        rule.get("renormalise", True), rule.get("scaling", 1.0))
    assert np.array_equal(np.asarray(experts), want_experts)
    np.testing.assert_allclose(np.asarray(gates), want_gates, rtol=1e-5)
    total = np.asarray(gates).sum(axis=1)
    if not rule.get("over_all") or rule.get("renormalise"):
        np.testing.assert_allclose(total, rule.get("scaling", 1.0),
                                   rtol=1e-5)
    else:       # the chosen probabilities of a softmax over all: under 1
        assert (total <= rule.get("scaling", 1.0) * (1 + 1e-6)).all()
        assert total.min() < 0.99 * rule.get("scaling", 1.0)


def test_granites_rule_is_the_default_and_its_program_is_the_parents():
    """``route`` with no rule named lowers to what PR 31's ``route`` lowered
    to: the top-k over the logits and one softmax, no multiply behind it."""
    u, router = jnp.ones((8, D), jnp.bfloat16), jnp.ones((D, WIDE))

    def parent(u, router):
        logits = jnp.dot(u, router.astype(u.dtype),
                         preferred_element_type=jnp.float32)
        top, experts = jax.lax.top_k(logits, K)
        return jax.nn.softmax(top.astype(jnp.float32), axis=-1), experts

    def now(u, router):
        return moe.route(u, router, K)

    assert str(jax.make_jaxpr(now)(u, router)) == \
        str(jax.make_jaxpr(parent)(u, router))
