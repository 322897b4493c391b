"""``ops/moe.py held_experts``: the buffer the sorted assignments go through
is as long as the share of the experts held here asks for, the way back is
choice-major with a float32 sum over the leading axis, and what comes out
is, bit for bit on the CPU, what the full-length layer of PR 28 gave, and
within rounding what a plain loop over tokens and choices gives."""
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


def row_buffers(jaxpr, width):
    """What the row traffic of a jaxpr is made of: the leading lengths of
    what each ``ragged_dot`` reads and writes (``ragged_dot``) and of every
    gather of (rows, ``width``) (``rows_in``), and the shape of every gather
    that yields a three-axis array of ``width`` (``back``)."""
    found = {"ragged_dot": [], "rows_in": [], "back": []}
    for eqn in equations(jaxpr):
        name, shape = eqn.primitive.name, eqn.outvars[0].aval.shape
        if name == "ragged_dot_general":
            found["ragged_dot"] += [eqn.invars[0].aval.shape[0], shape[0]]
        elif name == "gather" and shape[1:] == (width,):
            found["rows_in"].append(shape[0])
        elif name == "gather" and len(shape) == 3 and shape[2] == width:
            found["back"].append(shape)
    return found


def choice_sums(jaxpr, k, t, width):
    """``(operand dtype, axes)`` of every sum over an array of (``k``,
    ``t``, ``width``), after checking that no equation of the jaxpr yields
    one of (``t``, ``k``, ``width``): the choices never sit in a tile's
    sublanes."""
    sums = []
    for eqn in equations(jaxpr):
        assert all(v.aval.shape != (t, k, width) for v in eqn.outvars), eqn
        if eqn.primitive.name == "reduce_sum" and \
                eqn.invars[0].aval.shape == (k, t, width):
            sums.append((eqn.invars[0].aval.dtype, eqn.params["axes"]))
    return sums


#: two layers of the token family's tiny architecture: 8 experts, top 3
TINY = dict(GRANITE_TINY, num_hidden_layers=2)
BATCH, ROW = 16, 96     # 1,536 tokens, 4,608 assignments a layer


def step_jaxpr(shards):
    """The step in bfloat16: what is float32 in it was made so."""
    arch = gh.arch_from_config(TINY, shards, 0)
    params = jax.eval_shape(lambda: gh.init_params(arch, 0, jnp.bfloat16))
    rows = jax.ShapeDtypeStruct((BATCH, 2, ROW), jnp.int32)
    return jax.make_jaxpr(
        lambda p, r: gh.token_states(arch, p, r, jnp.bfloat16))(params, rows)


def test_with_half_the_experts_held_the_steps_buffers_are_the_bound_long():
    jaxpr = step_jaxpr(2).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 2                       # one a layer
    tokens, every = BATCH * ROW, BATCH * ROW * 3
    n = moe.held_rows(every, 4, 8)
    assert (n, every) == (3072, 4608)
    for cond in conds:
        # index 1 is the branch taken when the held count fits; either way
        # the rows come back choice-major, in the compute type
        full, compact = (row_buffers(b.jaxpr, 64)
                         for b in cond.params["branches"])
        assert compact == {"ragged_dot": [n] * 4, "rows_in": [n],
                           "back": [(3, tokens, 64)]}
        assert full == {"ragged_dot": [every] * 4, "rows_in": [every],
                        "back": [(3, tokens, 64)]}
        assert cond.outvars[0].aval.shape == (3, tokens, 64)
        assert cond.outvars[0].aval.dtype == jnp.bfloat16
    # nothing of the layer's row traffic is left outside the condition
    # (the one gather there is the embedding's)
    assert row_buffers(jaxpr.replace(eqns=[
        e for e in jaxpr.eqns if e.primitive.name != "cond"]), 64) == {
            "ragged_dot": [], "rows_in": [], "back": [(BATCH, ROW, 64)]}
    # the three choices are cast to float32, then summed over the leading
    # axis, once a layer; no (tokens, 3, 64) array anywhere in the step
    assert choice_sums(jaxpr, 3, tokens, 64) == [(jnp.float32, (0,))] * 2


def test_with_every_expert_held_the_step_has_one_length_and_no_condition():
    jaxpr = step_jaxpr(1).jaxpr
    assert not any(e.primitive.name in ("cond", "while")
                   for e in equations(jaxpr))
    tokens, every = BATCH * ROW, BATCH * ROW * 3
    assert row_buffers(jaxpr, 64) == {
        "ragged_dot": [every] * 8, "rows_in": [every] * 2,      # two layers
        "back": [(BATCH, ROW, 64)] + [(3, tokens, 64)] * 2}     # and embed
    assert choice_sums(jaxpr, 3, tokens, 64) == [(jnp.float32, (0,))] * 2
    # the layer alone, at another K and width: one gather in, one back, and
    # the sum's operand is float32 though everything before it is bfloat16
    args = layer_inputs(0, jnp.bfloat16, held=WIDE)
    alone = jax.make_jaxpr(lambda *a: moe.held_experts(*a, 0, args[6], WIDE)
                           )(*args[:5]).jaxpr
    assert row_buffers(alone, D) == {"ragged_dot": [T * K] * 4,
                                     "rows_in": [T * K], "back": [(K, T, D)]}
    assert choice_sums(alone, K, T, D) == [(jnp.float32, (0,))]
    back, = (e for e in equations(alone) if e.primitive.name == "gather"
             and e.outvars[0].aval.shape == (K, T, D))
    assert back.outvars[0].aval.dtype == jnp.bfloat16


# -- against a plain loop over tokens and choices ---------------------------------------

LOOP_T, LOOP_WIDE, LOOP_FIRST, LOOP_HELD = 512, 16, 6, 4
NOBODYS = LOOP_FIRST + 1        # a held expert that no token chooses
UNHELD_ONLY, PADDING = 40, 24   # tokens at the front / at the back
#: ``|got - want|`` over the norm of a token's terms, the worst token. The
#: products hand over in the compute type, so in bfloat16 the layer's own
#: roundings (2**-9 each) are what is held, and in float32 (2**-24) the
#: sum over the choices too
TOLERANCE = {jnp.float32: 4e-6, jnp.bfloat16: 3e-2}


def loop_inputs(seed, k, dtype):
    rng = np.random.default_rng(seed)
    open_to_all = np.delete(np.arange(LOOP_WIDE), NOBODYS)
    unheld = np.setdiff1d(np.arange(LOOP_WIDE),
                          np.arange(LOOP_FIRST, LOOP_FIRST + LOOP_HELD))
    experts = np.stack(
        [rng.permutation(unheld if i < UNHELD_ONLY else open_to_all)[:k]
         for i in range(LOOP_T)])
    gates = jax.nn.softmax(jnp.asarray(
        rng.standard_normal((LOOP_T, k)), jnp.float32), axis=-1)
    w_in = rng.standard_normal((LOOP_HELD, D, 2 * INNER)) / np.sqrt(D)
    w_out = rng.standard_normal((LOOP_HELD, INNER, D)) / np.sqrt(INNER)
    valid = np.arange(LOOP_T) < LOOP_T - PADDING
    return (jnp.asarray(rng.standard_normal((LOOP_T, D)), dtype), gates,
            jnp.asarray(experts, jnp.int32), jnp.asarray(w_in, dtype),
            jnp.asarray(w_out, dtype), LOOP_FIRST, jnp.asarray(valid))


def plain_loop(u, gates, experts, w_in, w_out, first, valid,
               accumulate=np.float64, activation="swiglu"):
    """``(sum over a token's held choices of gate * expert(u), the norm of
    those terms)``, token by token in float64 on the values the layer is
    handed; the running sum is kept in ``accumulate``. An expert is gated
    (``swiglu``) or not (``relu2``)."""
    u, gates, w_in, w_out = (np.asarray(a, np.float64)
                             for a in (u, gates, w_in, w_out))
    out = np.zeros(u.shape, np.float64)
    scale = np.zeros(len(u))
    for i in np.flatnonzero(np.asarray(valid)):
        total, terms = np.zeros(u.shape[1], accumulate), []
        for gate, expert in zip(gates[i], np.asarray(experts)[i] - first):
            if 0 <= expert < len(w_in):
                hidden = u[i] @ w_in[expert]
                if activation == "relu2":
                    hidden = np.maximum(hidden, 0.0) ** 2
                else:
                    a, b = np.split(hidden, 2)
                    hidden = a / (1 + np.exp(-a)) * b
                terms.append(gate * (hidden @ w_out[expert]))
                total = (total + terms[-1].astype(accumulate)
                         ).astype(accumulate)
        out[i] = total
        scale[i] = np.linalg.norm(terms) if terms else 0.0
    return out, scale


def worst_token(got, want, scale):
    """The largest distance of a token's row from ``want`` in units of the
    norm of its terms; a token with no term has to be exactly zero."""
    got = np.asarray(got, np.float64)
    assert not got[scale == 0].any()
    far = np.linalg.norm(got - want, axis=1)[scale > 0] / scale[scale > 0]
    return far.max()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("k", [3, 6, 10])
def test_held_experts_is_the_plain_loop_over_tokens_and_choices(k, dtype):
    args = loop_inputs(100 + k, k, dtype)
    experts, valid = np.asarray(args[2]), np.asarray(args[6])
    local = experts - LOOP_FIRST
    here = (local >= 0) & (local < LOOP_HELD) & valid[:, None]
    # the case is what the docstring says: an expert nobody chose, tokens
    # none of whose choices is held, padding rows that chose held experts
    assert not (experts == NOBODYS).any()
    assert (~here.any(axis=1) & valid).sum() >= UNHELD_ONLY
    assert ((local >= 0) & (local < LOOP_HELD))[~valid].any()
    want, scale = plain_loop(*args)
    assert (scale > 0).sum() > LOOP_T // 3 and not scale[~valid].any()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(moe.held_experts, static_argnums=(5, 7))(
            *args, LOOP_WIDE)
    assert got.dtype == jnp.float32
    assert worst_token(got, want, scale) < TOLERANCE[dtype]


#: nemotron_h's experts: non-gated, a quarter of 64 held, up to 22 choices
RELU2_T, RELU2_WIDE, RELU2_HELD, RELU2_INNER = 256, 64, 16, 48


def relu2_inputs(seed, k, dtype):
    """Every token chooses ``k`` different experts of 64, of which experts
    16 .. 31 are held; the experts are ``relu(u W)^2 V``, ``W`` (D, I)."""
    rng = np.random.default_rng(seed)
    experts = np.argsort(rng.random((RELU2_T, RELU2_WIDE)), axis=1)[:, :k]
    gates = 5.0 * jax.nn.softmax(jnp.asarray(
        rng.standard_normal((RELU2_T, k)), jnp.float32), axis=-1)
    w_in = rng.standard_normal((RELU2_HELD, D, RELU2_INNER)) / np.sqrt(D)
    w_out = rng.standard_normal((RELU2_HELD, RELU2_INNER, D)) \
        / np.sqrt(RELU2_INNER)
    return (jnp.asarray(rng.standard_normal((RELU2_T, D)), dtype), gates,
            jnp.asarray(experts, jnp.int32), jnp.asarray(w_in, dtype),
            jnp.asarray(w_out, dtype), RELU2_HELD,
            jnp.asarray(np.arange(RELU2_T) < RELU2_T - 8))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("k", [4, 22])
def test_relu2_experts_are_the_plain_loop_over_tokens_and_choices(k, dtype):
    """Non-gated ``relu2`` experts (nemotron_h's), up to the 22 choices of
    its router, a quarter of the experts held: the same tolerance as the
    gated ones. The first product is I wide, not 2I."""
    args = relu2_inputs(200 + k, k, dtype)
    want, scale = plain_loop(*args, activation="relu2")
    assert (scale > 0).sum() > RELU2_T // 2
    with jax.default_matmul_precision("highest"):
        got = jax.jit(moe.held_experts, static_argnums=(5, 7, 8))(
            *args, RELU2_WIDE, "relu2")
    assert got.dtype == jnp.float32
    assert worst_token(got, want, scale) < TOLERANCE[dtype]
    # the gated reading of the same weights is another layer
    assert worst_token(got, plain_loop(*args[:3], args[3][..., :32],
                                       args[4][:, :16], *args[5:])[0],
                       scale) > 0.1


def test_a_relu2_unit_and_what_the_moe_event_states_of_it():
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.standard_normal((10, D)), jnp.float32)
    w_in = jnp.asarray(rng.standard_normal((D, RELU2_INNER)), jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((RELU2_INNER, D)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = moe.gated_unit(u, w_in, w_out, activation="relu2")
    want = np.maximum(np.asarray(u, np.float64) @ np.asarray(w_in), 0) ** 2 \
        @ np.asarray(w_out)
    # float32 against float64, in units of the largest output
    assert np.abs(np.asarray(got) - want).max() < 1e-6 * np.abs(want).max()
    with pytest.raises(ValueError, match="none of"):
        moe.activate(u, "gelu")
    # the widths of both products come from the weights' shapes
    stated = moe.stated_products(
        1024, 22, 512, jnp.bfloat16,
        jax.ShapeDtypeStruct((128, 1024, 2688), jnp.bfloat16),
        jax.ShapeDtypeStruct((128, 2688, 1024), jnp.bfloat16))
    assert stated["widths"] == [[1024, 2688], [2688, 1024]]
    assert stated["rows"] == moe.held_rows(1024 * 22, 128, 512) == 7168


def test_a_bfloat16_sum_over_the_choices_is_noticed():
    """The same loop with its running sum in bfloat16 is further from the
    layer, in float32, than the tolerance allows: the comparison above
    holds the sum over the K choices to float32."""
    args = loop_inputs(110, 10, jnp.float32)
    want, scale = plain_loop(*args)
    rounded, _ = plain_loop(*args, accumulate=jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(moe.held_experts, static_argnums=(5, 7))(
            *args, LOOP_WIDE)
    assert worst_token(got, want, scale) < TOLERANCE[jnp.float32]
    assert worst_token(got, rounded, scale) > 100 * TOLERANCE[jnp.float32]


# -- the gate's rule ------------------------------------------------------------------

#: lfm2_moe's rule: a per-expert bias that moves the choice alone
SELECTION_BIAS = 0.05 * np.random.default_rng(12).standard_normal(WIDE)


def plain_gate(logits, k, rule, renormalise, scaling, selection_bias=None,
               eps=1e-6):
    """The three published rules in numpy, token by token; ``eps`` is what
    the sigmoid rule adds to the chosen sum."""
    gates, experts = [], []
    for row in np.asarray(logits, np.float64):
        if rule == "sigmoid":
            s = 1.0 / (1.0 + np.exp(-row))
            bias = 0.0 if selection_bias is None else selection_bias
            top = np.argsort(-(s + bias), kind="stable")[:k]
            g = s[top] / (s[top].sum() + eps) if renormalise else s[top]
        elif rule == "softmax_topk":
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
    dict(rule="softmax_topk", renormalise=False),           # deepseek_v2's
    dict(rule="softmax_topk", renormalise=True),
    dict(rule="softmax_topk", renormalise=False, scaling=2.5),
    dict(rule="sigmoid", selection_bias=SELECTION_BIAS),    # lfm2_moe's
    dict(rule="sigmoid", selection_bias=SELECTION_BIAS, scaling=2.5),
    dict(rule="sigmoid"),
    dict(rule="sigmoid", selection_bias=SELECTION_BIAS, scaling=5.0,
         renormalise_eps=1e-20),                            # nemotron_h's
], ids=["top_k_then_softmax", "softmax_then_top_k", "renormalised",
        "scaled", "sigmoid_biased", "sigmoid_biased_scaled",
        "sigmoid_unbiased", "sigmoid_biased_scaled_by_5"])
def test_route_under_each_rule_is_the_plain_computation(rule):
    rng = np.random.default_rng(11)
    u = jnp.asarray(rng.standard_normal((64, D)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((D, WIDE)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        gates, experts = moe.route(u, router, K, **rule)
        logits = jnp.dot(u, router)
    named = rule.get("rule", "topk_softmax")
    want_gates, want_experts = plain_gate(
        logits, K, named, rule.get("renormalise", True),
        rule.get("scaling", 1.0), rule.get("selection_bias"),
        rule.get("renormalise_eps", 1e-6))
    assert np.array_equal(np.asarray(experts), want_experts)
    np.testing.assert_allclose(np.asarray(gates), want_gates, rtol=1e-5)
    if rule.get("selection_bias") is not None:     # the bias moved a choice
        _, unbiased = plain_gate(logits, K, "sigmoid", True, 1.0)
        assert (np.sort(unbiased, 1) != np.sort(want_experts, 1)).any()
    total = np.asarray(gates).sum(axis=1)
    if named != "softmax_topk" or rule.get("renormalise"):
        np.testing.assert_allclose(total, rule.get("scaling", 1.0),
                                   rtol=1e-5)
    else:       # the chosen probabilities of a softmax over all: under 1
        assert (total <= rule.get("scaling", 1.0) * (1 + 1e-6)).all()
        assert total.min() < 0.99 * rule.get("scaling", 1.0)


@pytest.mark.parametrize("rule", ["topk_softmax", "softmax_topk"])
def test_a_selection_bias_belongs_to_the_sigmoid_rule_alone(rule):
    u, router = jnp.ones((8, D)), jnp.ones((D, WIDE))
    with pytest.raises(ValueError, match="selection bias"):
        moe.route(u, router, K, rule=rule,
                  selection_bias=jnp.asarray(SELECTION_BIAS))
    with pytest.raises(ValueError, match="none of"):
        moe.route(u, router, K, rule="softmax")


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


def test_top_22_of_512_under_a_bfloat16_router_swaps_what_float32_keeps():
    """nemotron_h's rule: a sigmoid over 512 experts with a bias of the
    seeded scale, the top 22, gates renormalised and scaled by 5; inputs
    and weights exact in bfloat16, 4,096 tokens of width 1,024. float32
    logits choose what float64 chooses for every token; logits rounded to
    bfloat16 swap an expert for 7.3% of them."""
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.standard_normal((4096, 1024)), jnp.bfloat16)
    w = jnp.asarray(0.02 * rng.standard_normal((1024, 512)), jnp.bfloat16)
    bias = (0.05 * rng.standard_normal(512)).astype(np.float32)
    exact = np.asarray(u, np.float64) @ np.asarray(w, np.float64)
    want = np.sort(np.argsort(-(1.0 / (1.0 + np.exp(-exact)) + bias),
                              axis=1)[:, :22], axis=1)

    def swapped(router_dtype):
        gates, chosen = moe.route(u, w, 22, router_dtype, rule="sigmoid",
                                  selection_bias=bias, scaling=5.0,
                                  renormalise_eps=1e-20)
        np.testing.assert_allclose(np.asarray(gates).sum(1), 5.0, rtol=1e-5)
        return float((np.sort(np.asarray(chosen), 1) != want).any(1).mean())

    assert swapped(jnp.float32) < 0.001 < 0.03 < swapped(jnp.bfloat16)
