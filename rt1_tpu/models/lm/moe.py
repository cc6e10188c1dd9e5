"""Sigmoid-routed experts, top-k, dropless, for the experts held here.

The layer is told which experts it holds (``spec.experts_held``).  It scores
and selects over ALL the router's experts, normalises the selected scores as
the whole model does, and returns the part of the result its own experts give:
``sum over selected AND held experts of w_i * E_i(x)``.  What the absent
experts would add is left out; no code stands in for them or for the exchange
that would bring their part here.

Dispatch is a sort, not a one-hot tensor: the tokens' assignments are ordered
by held expert (assignments to absent experts last), the rows are gathered in
that order, and one grouped matrix product runs over the rows of each held
expert (the megablox Pallas kernel on a TPU, ``lax.ragged_dot`` elsewhere).  The order is a
permutation, so both gathers (tokens to rows, rows back to the tokens' slots)
go back as gathers through its inverse, not as scatter-adds.  Every assignment of a
live token to a held expert is computed whatever the imbalance (a token is
live unless nothing reads its output: the padding at a packed sequence's
tail, which would otherwise all take the same experts): the row buffer has room for
all tokens x top-k assignments, and the grouped product skips the rows after
the last group, so the products' work follows the rows held.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from rt1_tpu.models.lm.layers import Leaf
from rt1_tpu.models.lm.spec import LMSpec

_STACK_INIT = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
                                               batch_axis=(0,))
MEGABLOX_TILING = (512, 1024, 1024)


class _Experts(nn.Module):
    """The stacks of the experts held here, (held, in, out) each."""

    spec: LMSpec

    @nn.compact
    def __call__(self):
        sp = self.spec
        held, d, f = sp.experts_held[1], sp.hidden_size, sp.moe_intermediate_size
        return tuple(Leaf("kernel", shape, _STACK_INIT, name=name)() for name, shape in (
            ("w1", (held, d, f)), ("w3", (held, d, f)), ("w2", (held, f, d))))


def route(x, router_kernel, expert_bias, spec: LMSpec):
    """(indices, weights) of each token's selected experts, float32.

    ``expert_bias`` (or None) enters the selection only."""
    logits = jnp.dot(x.astype(jnp.float32), router_kernel, precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    select = scores if expert_bias is None else scores + lax.stop_gradient(expert_bias)
    _, idx = lax.top_k(select, spec.experts_per_tok)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if spec.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return idx, weights * spec.routed_scaling_factor


def grouped_matmul(rows, stack, group_sizes):
    """``rows[i] @ stack[g]`` for the rows of group g; rows after the last
    group are not computed (their values are unspecified).  The megablox
    Pallas kernel on a TPU (it compiles for TPUs only), ``lax.ragged_dot``
    elsewhere."""
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        return megablox.gmm(rows, stack, group_sizes, rows.dtype, MEGABLOX_TILING)
    return lax.ragged_dot(rows, stack, group_sizes, preferred_element_type=rows.dtype)


@jax.custom_vjp
def rows_of_tokens(x, order, position, valid, is_held):
    """``rows[r] = x[order[r] // k]`` for the valid rows, zero after them.

    Its transpose is a gather too (``position`` inverts ``order``), so the
    way back is ``sum_k rows_to_slots`` and no scatter-add."""
    return jnp.where(valid[:, None], x[order // is_held.shape[1]], 0)


@jax.custom_vjp
def rows_to_slots(rows, order, position, valid, is_held):
    """``out[t, j] = rows[position[t, j]]`` where assignment (t, j) is held, else zero."""
    return jnp.where(is_held[..., None], rows[position], 0)


def _rows_of_tokens_fwd(x, *index):
    return rows_of_tokens(x, *index), index


def _rows_of_tokens_bwd(index, d_rows):
    d_slots = rows_to_slots(d_rows, *index)
    return (jnp.sum(d_slots.astype(jnp.float32), axis=1).astype(d_rows.dtype),) + (None,) * 4


def _rows_to_slots_fwd(rows, *index):
    return rows_to_slots(rows, *index), index


def _rows_to_slots_bwd(index, d_slots):
    order, _, valid, _ = index
    d_rows = jnp.where(valid[:, None], d_slots.reshape(order.shape[0], -1)[order], 0)
    return (d_rows,) + (None,) * 4


rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)
rows_to_slots.defvjp(_rows_to_slots_fwd, _rows_to_slots_bwd)


def held_experts_ffn(x, idx, weights, live, w1, w3, w2, spec: LMSpec):
    """(part of the layer's output from the held experts, rows per held expert).

    x: (tokens, d); idx, weights: (tokens, k); live: (tokens,) bool, the
    tokens whose output anything reads; w1, w3: (held, d, f); w2: (held, f, d)."""
    tokens, k = idx.shape
    first, held = spec.experts_held
    n = tokens * k
    with jax.named_scope("moe/dispatch"):
        local = idx - first
        is_held = (local >= 0) & (local < held) & live[:, None]
        key = jnp.where(is_held, local, held).reshape(n)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        # rows after the last group are zero and never computed
        valid = jnp.arange(n) < jnp.sum(group_sizes)
        position = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32), unique_indices=True)
        index = (order, jnp.where(is_held, position.reshape(tokens, k), 0), valid, is_held)
        rows = rows_of_tokens(x, *index)
    with jax.named_scope("moe/experts"):
        dtype = x.dtype
        both = grouped_matmul(
            rows, jnp.concatenate([w1, w3], axis=-1).astype(dtype), group_sizes)
        gate, up = jnp.split(both, 2, axis=-1)
        out_rows = grouped_matmul(jax.nn.silu(gate) * up, w2.astype(dtype), group_sizes)
    with jax.named_scope("moe/combine"):
        picked = rows_to_slots(out_rows, *index)
        out = jnp.sum(picked.astype(jnp.float32) * weights[..., None], axis=1).astype(dtype)
    return out, group_sizes


class RoutedFFN(nn.Module):
    spec: LMSpec

    @nn.compact
    def __call__(self, x, live=None) -> Tuple[Any, Dict[str, Any]]:
        """``live`` (b, s) bool, or None for all: the positions whose output
        anything reads.  The others (a packed sequence's tail padding) are
        routed like any token but take no row: their output is zero."""
        sp = self.spec
        b, s, d = x.shape
        live = jnp.ones((b * s,), bool) if live is None else live.reshape(b * s)
        flat = x.reshape(b * s, d)
        router_kernel = Leaf(
            "kernel", (d, sp.num_experts), nn.initializers.lecun_normal(), name="router")()
        # Named ``kernel`` like every leaf a configuration may want scaled at
        # the start: a deployment's balancing rule keeps this bias small, and a
        # bias of 0.02 moves an expert's share of the tokens by a quarter.
        bias = (Leaf("kernel", (sp.num_experts,), nn.initializers.zeros, name="expert_bias")()
                if sp.use_expert_bias else None)
        w1, w3, w2 = _Experts(sp, name="experts")()
        with jax.named_scope("moe/router"):
            idx, weights = route(flat, router_kernel, bias, sp)
        self.sow("intermediates", "selected", idx)
        # The row buffers have room for every assignment, eight times what the
        # held experts see when balanced: made again on the way back, not kept.
        out, group_sizes = jax.checkpoint(held_experts_ffn, static_argnums=(7,))(
            flat, idx, weights, live, w1, w3, w2, sp)
        rows = group_sizes.astype(jnp.float32)
        return out.reshape(b, s, d), {
            "rows_held": jnp.sum(rows), "rows_max": jnp.max(rows), "rows_mean": jnp.mean(rows)}
