"""The decoder: embedding, blocks as the spec lists them, final norm, the
output head over the vocabulary rows held here (the embedding again where the
spec ties them, else a leaf of its own), and the loss.

    h = x + Mixer(RMSNorm(x));  y = h + FFN(RMSNorm(h))

Where the spec has ``hc_mult`` = n > 1 the residual path is n streams a token
(hyper-connections, the form of arXiv:2512.24880), ``X`` in R^{n x d}: every
sublayer F (mixer, FFN) reads a mix of them and writes back through maps
computed per token by leaves of its own (``HyperConnection``),

    X <- H_res X + H_post^T F(RMSNorm(H_pre X))

with ``H_res`` made doubly stochastic by Sinkhorn iterations.  The embedding
is copied into the n streams and the streams are summed before the final
norm.  A block boundary is n times wider, so such a decoder's blocks are
recomputed on the way back (``nn.remat``).

Where the spec has a multi-token-prediction module (``mtp_layers`` 1, the
``deepseek_v3`` form): ``h'_i = M [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))]`` with
``h`` the trunk's output before its final norm, one routed block, a final norm
of its own, the trunk's embedding and head again; it predicts ``t_{i+2}`` and
the loss is ``L_next + mtp_loss_weight x L_mtp``, each a mean over its own
counted targets.

The head and the loss run a block of ``LOSS_BLOCK`` tokens at a time, so the
(tokens, vocabulary) float32 logits exist a block at a time only.
``next_token_loss`` is a ``jax.custom_vjp``: differentiated, its one loop makes
a block's logits once and from them the loss and both gradients (``d_x``, and
``d_head`` summed over the blocks in float32), and those two gradients are all
it keeps for the way back, where they are multiplied by the loss's cotangent: no
logits, no second pass over the head.  Its primal, ``next_token_loss_plain``, is
the same arithmetic as a plain function: what runs where nothing is
differentiated, and the oracle the rule's gradients are held to
(tests/test_lm_loss.py).
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Dict

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from rt1_tpu.models.lm import streams as streams_kernels
from rt1_tpu.models.lm.layers import (GQAttention, LatentAttention, Leaf, Linear, RMSNorm,
                                      ShortConv, SwiGLU)
from rt1_tpu.models.lm.moe import RoutedFFN
from rt1_tpu.models.lm.spec import IGNORE, BlockSpec, LMSpec

# tokens of one block of the output head and the loss (scripts/lm_kernel_probe.py --only head)
LOSS_BLOCK = 1024

_LOG = logging.getLogger(__name__)


def _loss_blocks(x, targets):
    """``x`` and the targets as (blocks, tokens a block, ...): blocks of
    ``LOSS_BLOCK`` tokens where that divides the tokens, else one block."""
    flat, flat_targets = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
    block = LOSS_BLOCK if flat.shape[0] % LOSS_BLOCK == 0 else flat.shape[0]
    return flat.reshape(-1, block, flat.shape[-1]), flat_targets.reshape(-1, block)


def _logits(xb, head):
    return jnp.einsum("td,vd->tv", xb, head, preferred_element_type=jnp.float32)


def next_token_loss_plain(x, head, targets):
    """Mean cross-entropy of ``x @ head.T`` (float32 logits) over the targets
    that count, a block of tokens at a time: the plain function.  What runs
    where nothing is differentiated, and the oracle of ``next_token_loss``'s
    gradients (``jax.grad`` of this is what they have to equal)."""
    xs, ts = _loss_blocks(x, targets)

    def one(args):
        xb, tb = args
        logits = _logits(xb, head)
        picked = jnp.take_along_axis(logits, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
        ce = jax.nn.logsumexp(logits, axis=-1) - picked
        return jnp.sum(jnp.where(tb != IGNORE, ce, 0.0))

    return jnp.sum(jax.lax.map(one, (xs, ts))) / jnp.maximum(jnp.sum(ts != IGNORE), 1)


@jax.custom_vjp
def next_token_loss(x, head, targets):
    """``next_token_loss_plain``, with its gradient made where its logits are
    made: differentiated, the one loop over the blocks makes a block's logits
    once and from them the loss and both gradients, and nothing of a block is
    kept or made again for the way back."""
    return next_token_loss_plain(x, head, targets)


@functools.lru_cache(maxsize=None)
def _announce_loss(block: int, blocks: int, accumulator: str) -> None:
    """Once a shape: how the head's gradient is made, as a log line."""
    _LOG.info("lm loss, gradient in the forward loop: %s",
              dict(block_tokens=block, blocks_a_pass=blocks, accumulator=accumulator))


def _loss_and_gradients(x, head, targets, accumulator=jnp.float32):
    """The forward rule: the loss and, as the only residuals, its gradients with
    respect to ``x`` and ``head`` for a cotangent of 1 (the gradient is linear
    in it): a block's ``d_logits = (softmax - onehot) * counted / count`` in
    float32, rounded to the operands' type for the two products as the default
    precision rounds them; ``d_head`` summed over the blocks in ``accumulator``
    (float32; the probe times bfloat16 beside it) and rounded once."""
    xs, ts = _loss_blocks(x, targets)
    count = jnp.maximum(jnp.sum(ts != IGNORE), 1).astype(jnp.float32)
    _announce_loss(xs.shape[1], xs.shape[0], jnp.dtype(accumulator).name)

    def one(d_head, args):
        xb, tb = args
        logits = _logits(xb, head)
        top = jnp.max(logits, axis=-1)
        exps = jnp.exp(logits - top[:, None])       # the block's one exponential
        sums = jnp.sum(exps, axis=-1)
        hit = jnp.arange(logits.shape[-1])[None, :] == tb[:, None]  # never at IGNORE
        counted = tb != IGNORE
        weight = counted / count
        ce = top + jnp.log(sums) - jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        d_logits = (exps * (weight / sums)[:, None] - jnp.where(hit, weight[:, None], 0.0)
                    ).astype(xb.dtype)
        d_xb = jnp.einsum("tv,vd->td", d_logits, head, preferred_element_type=jnp.float32)
        d_head = d_head + jnp.einsum("tv,td->vd", d_logits, xb,
                                     preferred_element_type=jnp.float32).astype(accumulator)
        return d_head, (jnp.sum(jnp.where(counted, ce, 0.0)), d_xb.astype(xb.dtype))

    d_head, (ce, d_x) = jax.lax.scan(one, jnp.zeros(head.shape, accumulator), (xs, ts))
    return jnp.sum(ce) / count, (d_x.reshape(x.shape), d_head.astype(head.dtype))


def _scaled_gradients(gradients, g):
    """The backward rule: the kept gradients times the loss's cotangent."""
    d_x, d_head = gradients
    return (g * d_x).astype(d_x.dtype), (g * d_head).astype(d_head.dtype), None


next_token_loss.defvjp(_loss_and_gradients, _scaled_gradients)


# What ``H_res``'s diagonal starts at before the exponential: the bias leaf holds
# the distance from this start (b_res = RES_START I + leaf), so that a leaf near
# zero, as a seed's draw or an init makes it, is a diagonally dominant ``H_res``
# (e^2 / (e^2 + 3) = 0.71 on the diagonal, 0.10 off it).  No nearer the identity:
# Sinkhorn's rounds converge at the square of the limit's second singular value,
# so 20 rounds leave a near-identity map's row sums off by percents (maps with
# N(0, 0.25) logits, largest gap over 100,000: 0.0016 at 2, 0.010 at 3, 0.017 at 4).
RES_START = 2.0


def sinkhorn(logits, iters: int, eps: float, clamp):
    """``exp(clamp(logits))`` over the two leading axes (rows, columns) made
    doubly stochastic: ``iters`` rounds of every row, then every column, divided
    by its sum + ``eps``; float32."""
    m = jnp.exp(jnp.clip(logits.astype(jnp.float32), *clamp))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


class HyperConnection(nn.Module):
    """The maps of one sublayer from the streams (n, b, s, d), float32 and
    token-minor: ``H_pre`` (n, b, s), ``H_post`` (n, b, s), ``H_res`` (n, n, b,
    s), and the largest gap of a row or column sum of ``H_res`` from 1.

        x~ = RMSNorm(vec(X));  [H~_pre | H~_post | vec(H~_res)] = alpha . (x~ phi) + b
        H_pre = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post)
        H_res = Sinkhorn(exp(clamp(H~_res + RES_START I)))

    ``alpha`` has one gate a map.  The norm's scale is folded into ``phi`` (x~
    phi = r (X (scale . phi)), r the inverse root mean square), so one pass over
    the streams gives both.

    ``enter`` gives the sublayer's input ``H_pre X`` with the maps and
    ``leave`` writes the streams back: by the Pallas kernels of ``streams.py``
    where ``streams.fits`` says so (a TPU, a shape that tiles), else by the
    plain functions below, which are the same arithmetic."""

    spec: LMSpec

    def setup(self):
        n, d = self.spec.hc_mult, self.spec.hidden_size
        self.norm = Leaf("scale", (n * d,), nn.initializers.ones)
        self.phi = Leaf("kernel", (n * d, n * (n + 2)), nn.initializers.normal(0.01))
        self.alpha = Leaf("scale", (3,), nn.initializers.constant(0.01))
        self.maps_bias = Leaf("bias", (n * (n + 2),), nn.initializers.zeros)

    def _leaves(self):
        """phi with the norm's scale folded in (n, d, n (n + 2)), as the
        streams are held; the gate and the bias of every raw map."""
        sp = self.spec
        n, d = sp.hc_mult, sp.hidden_size
        weights = (self.norm()[:, None] * self.phi()).astype(sp.dtype).reshape(n, d, n * (n + 2))
        return weights, self.alpha()[np.repeat(np.arange(3), [n, n, n * n])], self.maps_bias()

    def _maps(self, raw, gate, bias, rounds=None):
        sp = self.spec
        n = sp.hc_mult
        pre, post, res = jnp.split(
            raw * gate[:, None, None] + bias[:, None, None], [n, 2 * n], axis=0)
        res = res.reshape((n, n) + res.shape[1:]) + RES_START * jnp.eye(n)[:, :, None, None]
        h_res = (rounds or sinkhorn)(res, sp.hc_sinkhorn_iters, sp.hc_eps, sp.hc_clamp)
        err = jnp.maximum(jnp.max(jnp.abs(jnp.sum(h_res, axis=0) - 1.0)),
                          jnp.max(jnp.abs(jnp.sum(h_res, axis=1) - 1.0)))
        return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), h_res, err

    def __call__(self, streams):
        with jax.named_scope("hyper_connection/maps"):
            weights, gate, bias = self._leaves()
            return self._maps(_normed_projection(streams, weights, self.spec.norm_eps), gate, bias)

    def enter(self, streams):
        """``H_pre X`` (b, s, d), the streams as ``leave`` is to take them,
        ``H_post``, ``H_res`` and the sums' gap."""
        n, b, s, d = streams.shape
        if not streams_kernels.fits(b * s, d):
            h_pre, h_post, h_res, err = self(streams)
            return mix_in(streams, h_pre), streams, h_post, h_res, err
        with jax.named_scope("hyper_connection/maps"):
            weights, gate, bias = self._leaves()
        mixed, raw, streams = streams_kernels.maps_and_mix_in(
            streams, weights, gate[:n], bias[:n], self.spec.norm_eps)
        with jax.named_scope("hyper_connection/maps"):
            _, h_post, h_res, err = self._maps(raw, gate, bias, streams_kernels.rounds())
        return mixed, streams, h_post, h_res, err


def leave(streams, h_res, h_post, out):
    """``H_res X + H_post^T F``, by the path ``HyperConnection.enter`` took."""
    n, b, s, d = streams.shape
    if streams_kernels.fits(b * s, d):
        return streams_kernels.mix_out(streams, h_res, h_post, out)
    return mix_out(streams, h_res, h_post, out)


# The three passes over the streams are each under ``jax.checkpoint``: what they
# keep for the way back is their arguments (the streams as they are held, one
# stream's width of sublayer output, the maps), not the float32 copies of the
# streams that the products' transposes would otherwise keep (a float32 copy of
# four streams is 470 MB at 8,192 x 3,584, and a block has six such products).

@functools.partial(jax.checkpoint, static_argnums=(2,))
def _normed_projection(streams, weights, eps: float):
    """``RMSNorm(vec(X)) phi`` with the norm's scale folded into ``weights``:
    (n (n + 2), b, s), float32."""
    inverse_rms = jax.lax.rsqrt(jnp.mean(       # over vec(X): (b, s)
        jnp.square(streams.astype(jnp.float32)), axis=(0, -1)) + eps)
    return jnp.einsum("nbsd,ndk->kbs", streams, weights,
                      preferred_element_type=jnp.float32) * inverse_rms


@jax.checkpoint
def mix_in(streams, h_pre):
    """``H_pre X``: the sublayer's input, one row a token."""
    with jax.named_scope("hyper_connection/mix"):
        return sum(h_pre[j][..., None] * streams[j].astype(jnp.float32)
                   for j in range(streams.shape[0])).astype(streams.dtype)


@jax.checkpoint
def mix_out(streams, h_res, h_post, out):
    """``H_res X + H_post^T F``: the streams after the sublayer."""
    with jax.named_scope("hyper_connection/mix"):
        n = streams.shape[0]
        x32, out32 = streams.astype(jnp.float32), out.astype(jnp.float32)
        # each stream rounded before the stack: no float32 copy of all n is ever whole
        return jnp.stack([
            (sum(h_res[i, j][..., None] * x32[j] for j in range(n))
             + h_post[i][..., None] * out32).astype(streams.dtype)
            for i in range(n)])


class Block(nn.Module):
    spec: LMSpec
    block: BlockSpec

    @nn.compact
    def __call__(self, x, live):
        """x: (b, s, d), or the streams (n, b, s, d) where ``hc_mult`` > 1.
        Returns (x or streams, the routed layer's rows or None, the largest gap
        of the sublayers' ``H_res`` sums from 1 or None)."""
        sp = self.spec
        mixer = (ShortConv(sp, name="mixer") if self.block.mixer == "conv"
                 else LatentAttention(sp, name="mixer") if self.block.mixer == "latent_attention"
                 else GQAttention(sp, self.block.mixer, name="mixer"))
        if sp.hc_mult > 1:
            streams, err_mixer = self._mixer_sublayer(x, mixer)
            streams, rows, err_ffn = self._ffn_sublayer(streams, live)
            return streams, rows, jnp.maximum(err_mixer, err_ffn)
        h = x + mixer(RMSNorm(sp.norm_eps, sp.dtype, name="mixer_norm")(x))
        normed = RMSNorm(sp.norm_eps, sp.dtype, name="ffn_norm")(h)
        if self.block.ffn == "dense":
            return h + SwiGLU(sp, name="ffn")(normed), None, None
        out, rows = RoutedFFN(sp, name="ffn")(normed, live)
        return h + out, rows, None

    def _mixer_sublayer(self, streams, mixer):
        sp = self.spec
        mixed, streams, h_post, h_res, err = HyperConnection(sp, name="mixer_hc").enter(streams)
        mixed = mixer(RMSNorm(sp.norm_eps, sp.dtype, name="mixer_norm")(mixed))
        return leave(streams, h_res, h_post, mixed), err

    def _ffn_sublayer(self, streams, live):
        sp = self.spec
        mixed, streams, h_post, h_res, err = HyperConnection(sp, name="ffn_hc").enter(streams)
        normed = RMSNorm(sp.norm_eps, sp.dtype, name="ffn_norm")(mixed)
        if self.block.ffn == "dense":
            out, rows = SwiGLU(sp, name="ffn")(normed), None
        else:
            out, rows = RoutedFFN(sp, name="ffn")(normed, live)
        return leave(streams, h_res, h_post, out), rows, err


def live_positions(targets):
    """(b, s) bool: a position is live up to the last target that counts in its
    sequence."""
    counted = targets != IGNORE
    return jnp.flip(jnp.cumsum(jnp.flip(counted, 1), 1), 1) > 0


class MTPModule(nn.Module):
    """One multi-token-prediction module: the trunk's output before its final
    norm merged with the next token's embedding, one routed block (with
    hyper-connections of its own where the trunk has them: its streams start as
    copies of the merge), a final norm of its own."""

    spec: LMSpec

    @nn.compact
    def __call__(self, h, next_embedding, live):
        sp = self.spec
        merged = Linear(sp.hidden_size, sp.dtype, name="eh_proj")(jnp.concatenate([
            RMSNorm(sp.norm_eps, sp.dtype, name="hnorm")(h),
            RMSNorm(sp.norm_eps, sp.dtype, name="enorm")(next_embedding)], axis=-1))
        x = merged if sp.hc_mult == 1 else jnp.broadcast_to(merged, (sp.hc_mult,) + merged.shape)
        x, rows, err = block_class(sp)(
            sp, BlockSpec(sp.blocks[-1].mixer, "moe"), name="layer")(x, live)
        x = x if sp.hc_mult == 1 else jnp.sum(x.astype(jnp.float32), axis=0).astype(sp.dtype)
        return RMSNorm(sp.norm_eps, sp.dtype, name="final_norm")(x), rows, err


def block_class(spec: LMSpec):
    """``Block``, recomputed on the way back where its boundary is the streams."""
    return nn.remat(Block) if spec.recompute_blocks else Block


class DecoderLM(nn.Module):
    spec: LMSpec

    @nn.compact
    def __call__(self, observations, actions, train: bool = False,
                 return_logits: bool = False) -> Dict[str, Any]:
        """observations["tokens"], actions["targets"]: int32 (b, s), ids over
        the vocabulary rows held; a target of ``IGNORE`` does not count."""
        del train       # no dropout, no statistics
        sp = self.spec
        tokens, targets = observations["tokens"], actions["targets"]
        embedding = Leaf("embedding", (sp.vocab_held, sp.hidden_size),
                         nn.initializers.normal(0.02), name="embed")()
        if sp.mtp_layers:
            # one gather for the tokens and for the prediction module's next
            # tokens (the targets), so one scatter-add back into the table
            with jax.named_scope("embed"):
                x, following = jnp.split(embedding[jnp.concatenate(
                    [tokens, jnp.maximum(targets, 0)], axis=0)].astype(sp.dtype), 2, axis=0)
        else:
            with jax.named_scope("embed"):
                x = embedding[tokens].astype(sp.dtype)
        # A position is live up to the last target that counts in its
        # sequence.  Mixers are causal and padding is at the tail, so nothing
        # the loss reads depends on the positions after it: the routed layers
        # give them no rows (loss and gradients are exactly what they were).
        live = live_positions(targets)
        held, largest, mean, fallbacks = 0.0, 0.0, 0.0, 0.0
        sum_errs = []

        def count(rows, err):
            nonlocal held, largest, mean, fallbacks
            if rows is not None:
                held = held + rows["rows_held"]
                largest = largest + rows["rows_max"]
                mean = mean + rows["rows_mean"]
                fallbacks = fallbacks + rows["fallback"]
            if err is not None:
                sum_errs.append(err)

        if sp.hc_mult > 1:      # the embedding copied into the streams
            x = jnp.broadcast_to(x, (sp.hc_mult,) + x.shape)
        for i, block in enumerate(sp.blocks):
            x, rows, err = block_class(sp)(sp, block, name=f"layer_{i}")(x, live)
            count(rows, err)
        if sp.hc_mult > 1:      # and summed at the end
            x = jnp.sum(x.astype(jnp.float32), axis=0).astype(sp.dtype)
        trunk = x
        x = RMSNorm(sp.norm_eps, sp.dtype, name="final_norm")(x)
        if not sp.tie_word_embeddings:
            head_rows = Leaf("embedding", (sp.vocab_held, sp.hidden_size),
                             nn.initializers.normal(0.02), name="lm_head")()
        else:
            head_rows = embedding
        head = head_rows.astype(sp.dtype)
        with jax.named_scope("lm_loss"):
            out = {"loss": next_token_loss(x, head, targets)}
        if sp.mtp_layers:
            # The module at position i sees the trunk at i and the embedding of
            # token i + 1 (= targets[i]) and predicts token i + 2 (= targets[i +
            # 1]); it counts where both are there, so never at a sequence's last
            # position nor at the last before the padding.
            with jax.named_scope("mtp"):
                shifted = jnp.concatenate(
                    [targets[:, 1:], jnp.full_like(targets[:, :1], IGNORE)], axis=1)
                mtp_targets = jnp.where(targets != IGNORE, shifted, IGNORE)
                y, rows, err = MTPModule(sp, name="mtp")(
                    trunk, following, live_positions(mtp_targets))
                count(rows, err)
                with jax.named_scope("lm_loss"):
                    mtp_loss = next_token_loss(y, head, mtp_targets)
            out["loss_next"] = out["loss"]
            out["loss"] = out["loss"] + sp.mtp_loss_weight * mtp_loss
        if any(b.ffn == "moe" for b in sp.blocks):
            # summed over the expert layers
            out["counters"] = {
                "moe/assignments_held": held,
                "moe/load_max_over_mean": largest / jnp.maximum(mean, 1e-9),
                "moe/fallback_layers": fallbacks,
            }
        mixers = [b.mixer for b in sp.blocks]
        if "sliding_attention" in mixers:
            # static: which program a run timed (a decoder of full layers alone
            # keeps the outputs it had)
            out.setdefault("counters", {}).update({
                "attention/window_layers": jnp.float32(mixers.count("sliding_attention")),
                "attention/full_layers": jnp.float32(mixers.count("full_attention")),
            })
        # static: the passes through the head, each one loop that makes the
        # loss and, where it is differentiated, its gradients (next_token_loss)
        out.setdefault("counters", {})["lm_loss/grad_in_forward_passes"] = jnp.float32(
            1 + bool(sp.mtp_layers))
        if sp.mtp_layers:
            out["counters"]["mtp/loss"] = mtp_loss
        if sum_errs:
            # the largest gap of a row or column sum of any sublayer's H_res from 1
            out.setdefault("counters", {}).update({
                "hyper_connection/res_sum_err": jnp.max(jnp.stack(sum_errs)),
                "hyper_connection/sinkhorn_iters": jnp.float32(sp.hc_sinkhorn_iters),
                # static: the sublayers whose passes took the kernels (streams.py)
                "hyper_connection/fused_sublayers": jnp.float32(
                    len(sum_errs) * 2 * streams_kernels.fits(tokens.size, sp.hidden_size)),
            })
        if return_logits:
            out["logits"] = jnp.einsum("bsd,vd->bsv", x, head,
                                       preferred_element_type=jnp.float32)
        return out


def make_lm_step_loss_fn(model: DecoderLM):
    """The trainer's loss hook: ``(params, batch_stats, batch, rng, train) ->
    (loss, (out, batch_stats))``."""

    def loss_fn(params, batch_stats, batch, rng, train):
        del rng
        observations, actions = batch
        out = model.apply({"params": params}, observations, actions, train=train)
        return out["loss"], (out, batch_stats)

    return loss_fn
