"""A routed expert layer that is told which experts it holds.

The router keeps its published width: every token's logits over ALL experts,
the ``top_k`` largest, gates = softmax over those ``top_k`` logits (or, by
:func:`route`'s static rule, the ``top_k`` largest of the softmax over all,
or of a sigmoid over all with a bias that moves the choice alone).
Of the
selected experts only those in ``[first, first + held)`` live here (expert
parallelism: the others are on the chips that share the layer), and the
layer returns ``sum_e gate_e * expert_e(u)`` over the selected experts that
are held: what the absent ones would add is left out, by the plain reference
too. Each expert's rows go through one grouped matrix product, never a dense
product over all experts masked afterwards: on a TPU, at widths of whole
lanes, the Pallas kernel of ``kernels/grouped_matmul.py``, elsewhere
``jax.lax.ragged_dot`` (:func:`pallas_products` asks the kernel's own gate,
once a layer as the step is traced; nothing else selects one).

No capacity and no dropped token. The assignments are sorted by expert, the
held ones first, into a buffer whose length follows the share of the experts
held here (:func:`held_rows`: 1.25 times the share a uniform router would
send, at most every assignment). The held count is computed on the device
from the routing. Where it fits, the row gather, both products and the
fusion between them are that buffer long and the return trip reads from it
(device scope ``moe/held``); where it does not, the same steps run over a
buffer with room for every assignment (``moe/all``) and give the same bits.
A layer that holds every expert has the one length and no condition
(deepseek_v2 on one chip a layer).

The return trip is choice-major. Every assignment's output is gathered back
as (K, T, D), choice first, and the K float32 terms of a token are summed
over that leading axis. A (T, K, D) array would put the K choices in the
sublane position of a tile, where 10 (or 6) of them pad to the 16 rows of a
bfloat16 tile and every pass over the array moves the pad too; a leading K
pads nothing, whatever K is.

An expert is a unit ``act(u W) V`` under a static :data:`ACTIVATIONS` rule:
``swiglu`` (granite, dsv2, lfm2), the gated ``silu(u W[:, :I]) * (u W[:,
I:])`` of a ``W`` 2I wide, or ``relu2`` (nemotron_h), the non-gated
``relu(u W)^2`` of a ``W`` I wide.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import grouped_matmul as kernel     # imports no Pallas


#: :func:`route`'s rules: granite's, deepseek_v2's, lfm2_moe's and nemotron_h's
RULES = ("topk_softmax", "softmax_topk", "sigmoid")
#: an expert's activation (the module docstring)
ACTIVATIONS = ("swiglu", "relu2")


def route(u: jnp.ndarray, router: jnp.ndarray, top_k: int,
          router_dtype=jnp.float32, rule: str = "topk_softmax",
          renormalise: bool = True, scaling: float = 1.0,
          selection_bias: Optional[jnp.ndarray] = None,
          renormalise_eps: float = 1e-6) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(gates, experts)``, each (T, top_k): the router's choice for every
    token of ``u`` (T, D) over all ``router.shape[1]`` experts. Logits and
    gates are float32 (``router_dtype``: the tests lower it to show that the
    comparison notices): a logit rounded to bfloat16 swaps near-tied
    experts.

    The gate's ``rule`` is static. ``"topk_softmax"`` (granite's, the
    default): the ``top_k`` largest logits are chosen and the gates are the
    softmax over those. ``"softmax_topk"`` (deepseek_v2's ``scoring_func``
    softmax): the softmax runs over every expert's logit and the ``top_k``
    largest probabilities are chosen; they are divided by their sum only
    where ``renormalise`` (``norm_topk_prob``), and multiplied by
    ``scaling`` (``routed_scaling_factor``; the published deepseek_v2 code
    leaves the factor out where it renormalises, deepseek_v3's applies both,
    as here). ``"sigmoid"`` (lfm2_moe's, nemotron_h's): every logit goes
    through a sigmoid ``s``, the experts with the ``top_k`` largest ``s +
    selection_bias`` are chosen (the per-expert bias, float32, moves the
    choice and nothing else), and the gates are the chosen ``s``, divided
    by their sum + ``renormalise_eps`` (lfm2's published 1e-6, nemotron_h's
    1e-20) where ``renormalise``, times ``scaling``. ``selection_bias``
    belongs to the sigmoid rule alone."""
    if rule not in RULES:
        raise ValueError(f"route: rule {rule!r} is none of {RULES}")
    if selection_bias is not None and rule != "sigmoid":
        raise ValueError(f"route: a selection bias under rule {rule!r}")
    logits = jnp.dot(u, router.astype(u.dtype),
                     preferred_element_type=jnp.float32).astype(router_dtype)
    if rule == "sigmoid":
        s = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, experts = jax.lax.top_k(
            s if selection_bias is None
            else s + selection_bias.astype(jnp.float32), top_k)
        gates = jnp.take_along_axis(s, experts, axis=-1)
        if renormalise:
            gates = gates / (gates.sum(axis=-1, keepdims=True)
                             + renormalise_eps)
    elif rule == "topk_softmax":
        top, experts = jax.lax.top_k(logits, top_k)
        gates = jax.nn.softmax(top.astype(jnp.float32), axis=-1)
    else:
        gates, experts = jax.lax.top_k(
            jax.nn.softmax(logits.astype(jnp.float32), axis=-1), top_k)
        if renormalise:
            gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
    return (gates if scaling == 1.0 else gates * scaling), experts


def activate(hidden: jnp.ndarray, activation: str = "swiglu"
             ) -> jnp.ndarray:
    """An expert's hidden layer after its activation (:data:`ACTIVATIONS`):
    ``swiglu`` halves the width, ``relu2`` keeps it."""
    if activation == "relu2":
        return jnp.square(jax.nn.relu(hidden))
    if activation != "swiglu":
        raise ValueError(f"activation {activation!r} is none of "
                         f"{ACTIVATIONS}")
    gate, up = jnp.split(hidden, 2, axis=-1)
    return jax.nn.silu(gate) * up


def gated_unit(u: jnp.ndarray, w_in: jnp.ndarray, w_out: jnp.ndarray,
               activation: str = "swiglu") -> jnp.ndarray:
    """The shared expert, ``act(u W) V`` (:func:`activate`) for every
    token, float32 out."""
    hidden = jnp.dot(u, w_in, preferred_element_type=jnp.float32)
    return jnp.dot(activate(hidden, activation).astype(u.dtype), w_out,
                   preferred_element_type=jnp.float32)


#: Room over the uniform share of the assignments, ``held / wide``, in the
#: compact buffer. The benchmark's check (``benchmark/checks/``) refuses a run
#: whose local share is more than 0.05 off the expected one: for half the
#: experts that band ends at 0.55 of the assignments, and 0.5 * 1.25 = 0.625
#: leaves it and a layer's own skew room. A layer past it is still exact: it
#: takes the full-length path.
HELD_ROOM = 1.25


def held_rows(assignments: int, held: int, wide: int) -> int:
    """The static length of the buffer the sorted assignments go through
    when ``held`` of the router's ``wide`` experts live here: the uniform
    share times :data:`HELD_ROOM`, up to a multiple of 1,024 rows, and never
    more than all ``assignments``."""
    room = math.ceil(assignments * held / wide * HELD_ROOM)
    return min(assignments, -(-room // 1024) * 1024)


def pallas_products(length: int, dtype, w_in, w_out) -> Optional[str]:
    """Why the two products of ``length`` rows of ``dtype`` through experts
    ``w_in`` (E, D, 2I or I) and ``w_out`` (E, I, D) do not both run in the
    Pallas kernel, or ``None`` where they do
    (``kernel.grouped_matmul_refusal`` of each: the backend and the
    shapes)."""
    for w in (w_in, w_out):
        refusal = kernel.grouped_matmul_refusal(
            jax.ShapeDtypeStruct((length, w.shape[1]), dtype), w)
        if refusal is not None:
            return refusal
    return None


def stated_products(tokens: int, top_k: int, wide: int, dtype, w_in, w_out
                    ) -> dict:
    """What a ``moe`` event says of the layer :func:`held_experts` runs for
    ``tokens`` tokens of ``dtype`` a dispatch (feature values do not say
    which products ran): the answer of the gate the step itself asks, at
    the compact buffer's length."""
    held = w_in.shape[0]
    rows = held_rows(tokens * top_k, held, wide)
    fallback = pallas_products(rows, dtype, w_in, w_out)
    widths = [list(w_in.shape[1:]), list(w_out.shape[1:])]
    return dict(
        products="pallas" if fallback is None else "ragged_dot", rows=rows,
        experts=held, widths=widths, fallback=fallback,
        tiles=None if fallback is not None else [
            list(kernel.tiles_for(k, n, jnp.dtype(dtype).itemsize))
            for k, n in widths])


def held_experts(u: jnp.ndarray, gates: jnp.ndarray, experts: jnp.ndarray,
                 w_in: jnp.ndarray, w_out: jnp.ndarray, first: int,
                 valid: jnp.ndarray, wide: int, activation: str = "swiglu"
                 ) -> jnp.ndarray:
    """This chip's part of the routed layer, (T, D) float32.

    ``u`` (T, D); ``gates`` / ``experts`` (T, K) from :func:`route` over
    ``wide`` experts; ``w_in`` (E, D, 2I or I by ``activation``,
    :func:`activate`) and ``w_out`` (E, I, D) are experts
    ``first`` .. ``first + E - 1``; ``valid`` (T,) is false for padding,
    which is routed nowhere. Assignments to an expert that is not held sort
    behind the held ones and fall outside every group, so they are never
    multiplied; past :func:`held_rows` they are not gathered either."""
    t, k = experts.shape
    held = w_in.shape[0]
    local = experts - first
    here = (local >= 0) & (local < held) & valid[:, None]
    group = jnp.where(here, local, held).reshape(-1)            # (T*K,)
    order = jnp.argsort(group, stable=True)
    sizes = (group[:, None] == jnp.arange(held)).sum(axis=0, dtype=jnp.int32)
    place = jnp.argsort(order)      # the permutation's inverse, by a sort

    def through(length: int) -> jnp.ndarray:
        """Every assignment's expert output, (K, T, D) in (choice, token)
        order, by way of the first ``length`` sorted assignments, which
        have to hold every held one."""
        rows = u[order[:length] // k]                           # (length, D)
        # the products accumulate in float32 and hand over in the compute
        # type, in either form
        product = kernel.grouped_matmul \
            if pallas_products(length, u.dtype, w_in, w_out) is None \
            else partial(
                jax.lax.ragged_dot, preferred_element_type=u.dtype)
        hidden = product(rows, w_in, sizes)
        out = product(activate(hidden, activation), w_out, sizes)
        # an assignment that is not held has its place behind the held ones:
        # clamped into the buffer, to a row the caller masks
        at = place if length == t * k else jnp.minimum(place, length - 1)
        return out[at.reshape(t, k).T]

    def under(name: str, length: int):
        def branch():
            with jax.named_scope(name):
                return through(length)
        return branch

    n = held_rows(t * k, held, wide)
    # only what depends on the length sits under the condition: what both
    # branches share the compiler moves out of them, under either's scope
    back = through(n) if n == t * k else jax.lax.cond(
        sizes.sum() <= n, under("held", n), under("all", t * k))
    # rows past the last group hold nothing that was computed and are
    # masked, not trusted to be zero
    picked = jnp.where(here.T[..., None], back, 0)
    weight = jnp.where(here, gates, 0.0).T
    return jnp.sum(weight[..., None] * picked.astype(jnp.float32), axis=0)
