"""Routed experts (sigmoid or softmax scores), top-k, dropless, for the
experts held here, and the shared expert beside them where the spec has one.

The layer is told which experts it holds (``spec.experts_held``).  It scores
and selects over ALL the router's experts, normalises the selected scores as
the whole model does, and returns the part of the result its own experts give:
``sum over selected AND held experts of w_i * E_i(x)``.  What the absent
experts would add is left out; no code stands in for them or for the exchange
that would bring their part here.

A shared expert (``spec.n_shared_experts``) is a SwiGLU of the experts' width
that every token passes and every chip of an expert-parallel group computes
alike: ``y = sum over selected AND held of w_i E_i(x) + S(x)``; where the
shares of a group are added up it counts once.

Dispatch is a sort, not a one-hot tensor: the tokens' assignments are ordered
by held expert (assignments to absent experts last), the rows are gathered in
that order, and one grouped matrix product runs over the rows of each held
expert (the megablox Pallas kernel on a TPU, ``lax.ragged_dot`` elsewhere).
Every assignment of a live token to a held expert is computed whatever the
imbalance (a token is live unless nothing reads its output: the padding at a
packed sequence's tail, which would otherwise all take the same experts).

The buffers follow the rows held.  Of ``n`` = tokens x top-k assignment slots
a layer that holds ``held`` of ``num_experts`` experts sees ``n x held /
num_experts`` when the router is balanced; its row buffer has
``row_capacity`` = twice that, in whole row tiles of the grouped product, at
most ``n``.  A step whose held rows fit takes the row path: the first
``capacity`` assignments in dispatch order are gathered, computed, weighed in
float32 and added up per token, every array ``capacity`` rows long, every
move one op over the whole buffer (``token_sums`` says what its way back
moves, and why it is written by hand).  A step
whose rows do not fit takes the slot path under ``lax.cond`` in the same
compiled step: buffers of all ``n`` slots, the order a permutation, so both
gathers (tokens to rows, rows back to the tokens' slots) go back as gathers
through its inverse.  A row's arithmetic is the same on both.  A layer whose
capacity is all its slots (it holds half the experts or more) runs the slot
path with no branch.  ``held_experts_ffn`` says which path a step took; the
model counts the layers that took the slots as ``moe/fallback_layers``.

Nothing of a routed layer is kept for the way back but its arguments and the
dispatch order: the way back makes its branch's forward again, inside the
branch, so no buffer outlives a branch and none is sized for both.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from rt1_tpu.models.lm.layers import Leaf, SwiGLU
from rt1_tpu.models.lm.spec import LMSpec

_STACK_INIT = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
                                               batch_axis=(0,))
# Rows of one tile of the grouped product: a group's rows are padded to it, and
# the row buffer is a whole number of them.
ROW_TILE = 512


def megablox_tiling(k: int, n: int) -> Tuple[int, int, int]:
    """The grouped product's tiles (rows, contraction, columns) for stacks of
    (k, n).  A side takes 1,024 where 512 divides it (2048, 3072; 1536 runs as
    one whole tile and a half one, masked: the tiling the chip chose at those
    widths, PERF.md section 6, PR 27), else the largest multiple of the 128
    lanes up to 1,024 that divides it (2304 -> 768, 1792 -> 896, 896 -> 896:
    no tile of 1,024 divides these; both products of 16 experts over 32,768
    rows, both ways, read 10.27 ms against 11.50 at (512, 1024, 1024) and 11.91
    at (512, 512, 512): scripts/lm_kernel_probe.py --only experts, PERF.md
    section 6, PR 31)."""
    def side(size: int) -> int:
        if size % 512 == 0:
            return 1024
        return max((t for t in range(128, 1025, 128) if size % t == 0), default=1024)

    return ROW_TILE, side(k), side(n)


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
    if spec.scoring_func == "softmax":
        # over all the router's experts, before the selection; the selected
        # ones' shares of their own sum are the weights
        scores = jax.nn.softmax(logits, axis=-1)
        _, idx = lax.top_k(scores, spec.experts_per_tok)
        weights = jnp.take_along_axis(scores, idx, axis=-1)
        if spec.norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        scaling = spec.routed_scaling_factor
        return idx, weights if scaling == 1.0 else weights * scaling
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

        return megablox.gmm(rows, stack, group_sizes, rows.dtype,
                            megablox_tiling(*stack.shape[1:]))
    return lax.ragged_dot(rows, stack, group_sizes, preferred_element_type=rows.dtype)


def row_capacity(n: int, held: int, num_experts: int) -> int:
    """Rows of the row buffer for ``n`` assignment slots: twice the balanced
    share of the held experts, in whole row tiles of the grouped product,
    never more than ``n``."""
    tile = ROW_TILE
    balanced = -(-n * held // num_experts)
    return min(n, -(-2 * balanced // tile) * tile)


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


@jax.custom_vjp
def held_rows(x, token, valid):
    """``rows[r] = x[token[r]]`` for the valid rows of a compact buffer, zero
    after them.  The way back adds the rows up per token in float32, as the
    slot path sums a token's slots."""
    return jnp.where(valid[:, None], x[token], 0)


def _held_rows_fwd(x, token, valid):
    return held_rows(x, token, valid), (token, valid, x.shape[0])


def _held_rows_bwd(res, d_rows):
    token, valid, tokens = res
    kept = jnp.where(valid[:, None], d_rows, 0).astype(jnp.float32)
    d_x = jnp.zeros((tokens, kept.shape[1]), jnp.float32).at[token].add(kept)
    return d_x.astype(d_rows.dtype), None, None


held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


@jax.custom_vjp
def token_sums(out_rows, weights, token, slot, valid):
    """``out[t] = sum of weights[t, j] * out_rows[r]`` over the valid rows of
    a compact buffer, row r that of assignment ``slot[r]`` = t x top-k + j
    (``token[r]`` = t), weighed and added per token in float32.

    The way back is written by hand for the bytes it moves: jax's own
    transpose of the scatter-add widens the (tokens, d) cotangent to float32
    and gathers float32 rows; here the rows are gathered as they are and
    widened after, half the bytes, and the same numbers."""
    weight = weights.reshape(-1).at[slot].get(unique_indices=True)
    weighed = jnp.where(valid[:, None], out_rows, 0).astype(jnp.float32) * weight[:, None]
    out = jnp.zeros((weights.shape[0], out_rows.shape[1]), jnp.float32).at[token].add(weighed)
    return out.astype(out_rows.dtype)


def _token_sums_fwd(out_rows, weights, *index):
    return token_sums(out_rows, weights, *index), (out_rows, weights, index)


def _token_sums_bwd(res, d_out):
    out_rows, weights, (token, slot, valid) = res
    weight = weights.reshape(-1).at[slot].get(unique_indices=True)
    d_weighed = d_out[token].astype(jnp.float32)
    d_out_rows = jnp.where(valid[:, None], d_weighed * weight[:, None], 0).astype(out_rows.dtype)
    d_weight = jnp.sum(
        d_weighed * jnp.where(valid[:, None], out_rows, 0).astype(jnp.float32), axis=1)
    d_weights = jnp.zeros((weights.size,), jnp.float32).at[slot].add(
        d_weight, unique_indices=True)
    return (d_out_rows, d_weights.reshape(weights.shape).astype(weights.dtype)) + (None,) * 3


token_sums.defvjp(_token_sums_fwd, _token_sums_bwd)


def _experts(rows, w1, w3, w2, group_sizes):
    dtype = rows.dtype
    both = grouped_matmul(rows, jnp.concatenate([w1, w3], axis=-1).astype(dtype), group_sizes)
    gate, up = jnp.split(both, 2, axis=-1)
    return grouped_matmul(jax.nn.silu(gate) * up, w2.astype(dtype), group_sizes)


def _slot_index(order, group_sizes, is_held):
    tokens, k = is_held.shape
    n = tokens * k
    # rows after the last group are zero and never computed
    valid = jnp.arange(n) < jnp.sum(group_sizes)
    position = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)
    return order, jnp.where(is_held, position.reshape(tokens, k), 0), valid, is_held


def _slot_dispatch(x, index):
    return rows_of_tokens(x, *index)


def _slot_combine(out_rows, weights, index):
    picked = rows_to_slots(out_rows, *index)
    return jnp.sum(picked.astype(jnp.float32) * weights[..., None], axis=1).astype(
        out_rows.dtype)


def _row_index(capacity, order, group_sizes, is_held):
    slot = order[:capacity]
    return slot // is_held.shape[1], slot, jnp.arange(capacity) < jnp.sum(group_sizes)


def _row_dispatch(x, index):
    token, _, valid = index
    return held_rows(x, token, valid)


def _row_combine(out_rows, weights, index):
    return token_sums(out_rows, weights, *index)


def _path(capacity):
    """A path between the tokens and the experts' rows: ``index(order,
    group_sizes, is_held)``, ``dispatch(x, index) -> rows`` and
    ``combine(out_rows, weights, index) -> out``.  By slots (``capacity``
    None): buffers of all tokens x top-k rows, which fit whatever the
    imbalance.  By rows: buffers of ``capacity`` rows, for a step whose held
    rows fit, so that the first ``capacity`` assignments in dispatch order
    are all of them."""
    if capacity is None:
        return _slot_index, _slot_dispatch, _slot_combine
    return functools.partial(_row_index, capacity), _row_dispatch, _row_combine


# Both are jitted so that a model's routed layers, alike in shape, trace each
# path once and not once a layer.
@functools.partial(jax.jit, static_argnums=(0,))
def _forward(capacity, x, weights, w1, w3, w2, order, group_sizes, is_held):
    make_index, dispatch, combine = _path(capacity)
    with jax.named_scope("moe/dispatch"):
        index = make_index(order, group_sizes, is_held)
        rows = dispatch(x, index)
    with jax.named_scope("moe/experts"):
        out_rows = _experts(rows, w1, w3, w2, group_sizes)
    with jax.named_scope("moe/combine"):
        return combine(out_rows, weights, index)


@functools.partial(jax.jit, static_argnums=(0,))
def _backward(capacity, d_out, x, weights, w1, w3, w2, order, group_sizes, is_held):
    """The forward again, stage by stage, and each stage's way back under the
    stage's own scope."""
    make_index, dispatch, combine = _path(capacity)
    with jax.named_scope("moe/dispatch"):
        index = make_index(order, group_sizes, is_held)
        rows, back_dispatch = jax.vjp(lambda x: dispatch(x, index), x)
    with jax.named_scope("moe/experts"):
        out_rows, back_experts = jax.vjp(
            lambda rows, w1, w3, w2: _experts(rows, w1, w3, w2, group_sizes), rows, w1, w3, w2)
    with jax.named_scope("moe/combine"):
        back_combine = jax.vjp(
            lambda out_rows, weights: combine(out_rows, weights, index), out_rows, weights)[1]
        d_out_rows, d_weights = back_combine(d_out)
    with jax.named_scope("moe/experts"):
        d_rows, d_w1, d_w3, d_w2 = back_experts(d_out_rows)
    with jax.named_scope("moe/dispatch"):
        (d_x,) = back_dispatch(d_rows)
    return d_x, d_weights, d_w1, d_w3, d_w2


def _fits(capacity, group_sizes):
    return jnp.sum(group_sizes) <= capacity


def _either(run, capacity, *operands):
    """``run`` (``_forward`` or ``_backward``; the operands end with order,
    group_sizes, is_held) by rows where the step's held rows fit
    ``capacity``, by slots where they do not; by slots alone, with no branch,
    where ``capacity`` is all the slots."""
    by_slots = functools.partial(run, None)
    group_sizes, is_held = operands[-2:]
    if capacity >= is_held.size:
        return by_slots(*operands)
    return lax.cond(_fits(capacity, group_sizes),
                    functools.partial(run, capacity), by_slots, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed(capacity, x, weights, w1, w3, w2, order, group_sizes, is_held):
    """The held experts' part of the layer's output.  Nothing is kept for the
    way back but the arguments: it makes its branch's forward again, as under
    ``jax.checkpoint``, and both ways of a branch are inside the branch."""
    return _either(_forward, capacity, x, weights, w1, w3, w2, order, group_sizes, is_held)


def _routed_fwd(capacity, *args):
    return _routed(capacity, *args), args


def _routed_bwd(capacity, args, d_out):
    # as jax.checkpoint ties what it makes again to the cotangent: without
    # it the compiler is free to make every layer's forward again early
    args, d_out = lax.optimization_barrier((args, d_out))
    grads = _either(_backward, capacity, d_out, *args)
    # what reads the stacks' gradients (the optimizer, the health pack's sums)
    # stays outside the branches, one copy of it and not two
    return lax.optimization_barrier(tuple(grads)) + (None,) * 3


_routed.defvjp(_routed_fwd, _routed_bwd)


def held_experts_ffn(x, idx, weights, live, w1, w3, w2, spec: LMSpec, capacity: int):
    """(part of the layer's output from the held experts, rows per held
    expert, whether the rows overflowed ``capacity`` and the slots were used).

    x: (tokens, d); idx, weights: (tokens, k); live: (tokens,) bool, the
    tokens whose output anything reads; w1, w3: (held, d, f); w2: (held, f, d);
    capacity: rows of the row buffer, ``row_capacity`` of the layer's shapes."""
    tokens, k = idx.shape
    first, held = spec.experts_held
    n = tokens * k
    with jax.named_scope("moe/dispatch"):
        local = idx - first
        is_held = (local >= 0) & (local < held) & live[:, None]
        key = jnp.where(is_held, local, held).reshape(n)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    out = _routed(capacity, x, weights, w1, w3, w2, order, group_sizes, is_held)
    return out, group_sizes, ~_fits(capacity, group_sizes)


def shared_expert(spec: LMSpec, name: str = "shared_expert") -> SwiGLU:
    """The shared expert: a SwiGLU of the routed experts' width (times
    ``n_shared_experts``) that every token passes, under ``moe/shared``."""
    return SwiGLU(spec, spec.moe_intermediate_size * spec.n_shared_experts, "moe/shared",
                  name=name)


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
        if sp.recompute_blocks:
            # The stacks go into the routed layer in the compute type, so their
            # gradients come out of it in that type and are widened where Adam
            # reads them, as every other kernel's are: a step that holds every
            # gradient until its norm is known (the guard) holds half the bytes.
            # Only where the block is made again on the way back: elsewhere the
            # cast copies would be kept from the forward pass to the backward.
            w1, w3, w2 = (w.astype(sp.dtype) for w in (w1, w3, w2))
        with jax.named_scope("moe/router"):
            idx, weights = route(flat, router_kernel, bias, sp)
        self.sow("intermediates", "selected", idx)
        out, group_sizes, fell_back = held_experts_ffn(
            flat, idx, weights, live, w1, w3, w2, sp,
            row_capacity(b * s * sp.experts_per_tok, sp.experts_held[1], sp.num_experts))
        rows = group_sizes.astype(jnp.float32)
        out = out.reshape(b, s, d)
        if sp.n_shared_experts:
            out = out + shared_expert(sp)(x)
        return out, {
            "rows_held": jnp.sum(rows), "rows_max": jnp.max(rows), "rows_mean": jnp.mean(rows),
            "fallback": fell_back.astype(jnp.float32)}
