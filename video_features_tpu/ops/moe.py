"""A routed expert layer that is told which experts it holds.

The router keeps its published width: every token's logits over ALL experts,
the ``top_k`` largest, gates = softmax over those ``top_k`` logits. Of the
selected experts only those in ``[first, first + held)`` live here (expert
parallelism: the others are on the chips that share the layer), and the
layer returns ``sum_e gate_e * expert_e(u)`` over the selected experts that
are held: what the absent ones would add is left out, by the plain reference
too. No capacity and no dropped token: the assignments are sorted by expert
into a buffer that has room for every one of them, and each expert's rows go
through one grouped matrix product (``jax.lax.ragged_dot``), never a dense
product over all experts masked afterwards.

An expert is a gated unit: ``(silu(u W[:, :I]) * (u W[:, I:])) V``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def route(u: jnp.ndarray, router: jnp.ndarray, top_k: int,
          router_dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(gates, experts)``, each (T, top_k): the router's choice for every
    token of ``u`` (T, D) over all ``router.shape[1]`` experts. Logits and
    gates are float32 (``router_dtype``: the tests lower it to show that the
    comparison notices): a logit rounded to bfloat16 swaps near-tied
    experts."""
    logits = jnp.dot(u, router.astype(u.dtype),
                     preferred_element_type=jnp.float32).astype(router_dtype)
    top, experts = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(top.astype(jnp.float32), axis=-1), experts


def gated_unit(u: jnp.ndarray, w_in: jnp.ndarray, w_out: jnp.ndarray
               ) -> jnp.ndarray:
    """The shared expert: ``(silu(u W[:, :I]) * (u W[:, I:])) V`` for every
    token, float32 out."""
    hidden = jnp.dot(u, w_in, preferred_element_type=jnp.float32)
    gate, up = jnp.split(hidden, 2, axis=-1)
    return jnp.dot((jax.nn.silu(gate) * up).astype(u.dtype), w_out,
                   preferred_element_type=jnp.float32)


def held_experts(u: jnp.ndarray, gates: jnp.ndarray, experts: jnp.ndarray,
                 w_in: jnp.ndarray, w_out: jnp.ndarray, first: int,
                 valid: jnp.ndarray) -> jnp.ndarray:
    """This chip's part of the routed layer, (T, D) float32.

    ``u`` (T, D); ``gates`` / ``experts`` (T, K) from :func:`route`;
    ``w_in`` (E, D, 2I) and ``w_out`` (E, I, D) are experts ``first`` ..
    ``first + E - 1``; ``valid`` (T,) is false for padding, which is routed
    nowhere. Assignments to an expert that is not held sort behind the held
    ones and fall outside every group, so they are never multiplied."""
    t, k = experts.shape
    held = w_in.shape[0]
    local = experts - first
    here = (local >= 0) & (local < held) & valid[:, None]
    group = jnp.where(here, local, held).reshape(-1)            # (T*K,)
    order = jnp.argsort(group, stable=True)
    sizes = (group[:, None] == jnp.arange(held)).sum(axis=0, dtype=jnp.int32)
    rows = u[order // k]                                        # (T*K, D)
    # the products accumulate in float32 and hand over in the compute type
    hidden = jax.lax.ragged_dot(rows, w_in, sizes,
                                preferred_element_type=u.dtype)
    gate, up = jnp.split(hidden, 2, axis=-1)
    out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_out, sizes,
                             preferred_element_type=u.dtype)
    # back to (token, choice) order; rows past the last group hold nothing
    # that was computed and are masked, not trusted to be zero
    place = jnp.argsort(order)      # the permutation's inverse, by a sort
    picked = jnp.where(here[..., None], out[place].reshape(t, k, -1), 0)
    weight = jnp.where(here, gates, 0.0)
    return jnp.sum(weight[..., None] * picked.astype(jnp.float32), axis=1)
