"""The decoder: embedding, blocks as the spec lists them, final norm, the
output head over the vocabulary rows held here (the embedding again where the
spec ties them, else a leaf of its own), and the loss.

    h = x + Mixer(RMSNorm(x));  y = h + FFN(RMSNorm(h))
"""

from __future__ import annotations

from typing import Any, Dict

import flax.linen as nn
import jax
import jax.numpy as jnp

from rt1_tpu.models.lm.layers import GQAttention, Leaf, RMSNorm, ShortConv, SwiGLU
from rt1_tpu.models.lm.moe import RoutedFFN
from rt1_tpu.models.lm.spec import IGNORE, BlockSpec, LMSpec

LOSS_BLOCK = 2048   # tokens of one block of the output head and the loss


def next_token_loss(x, head, targets):
    """Mean cross-entropy of ``x @ head.T`` (float32 logits) over the targets
    that count, a block of tokens at a time, each block under
    ``jax.checkpoint``: a block's (tokens, vocabulary) logits are made, reduced
    and made again on the way back, never kept for the whole batch."""
    flat, flat_targets = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
    block = LOSS_BLOCK if flat.shape[0] % LOSS_BLOCK == 0 else flat.shape[0]

    @jax.checkpoint
    def one(args):
        xb, tb = args
        logits = jnp.einsum("td,vd->tv", xb, head, preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
        ce = jax.nn.logsumexp(logits, axis=-1) - picked
        return jnp.sum(jnp.where(tb != IGNORE, ce, 0.0))

    total = jnp.sum(jax.lax.map(one, (flat.reshape(-1, block, flat.shape[-1]),
                                      flat_targets.reshape(-1, block))))
    return total / jnp.maximum(jnp.sum(flat_targets != IGNORE), 1)


class Block(nn.Module):
    spec: LMSpec
    block: BlockSpec

    @nn.compact
    def __call__(self, x, live):
        sp = self.spec
        mixer = (ShortConv(sp, name="mixer") if self.block.mixer == "conv"
                 else GQAttention(sp, self.block.mixer, name="mixer"))
        h = x + mixer(RMSNorm(sp.norm_eps, sp.dtype, name="mixer_norm")(x))
        normed = RMSNorm(sp.norm_eps, sp.dtype, name="ffn_norm")(h)
        if self.block.ffn == "dense":
            return h + SwiGLU(sp, name="ffn")(normed), None
        out, rows = RoutedFFN(sp, name="ffn")(normed, live)
        return h + out, rows


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
        with jax.named_scope("embed"):
            x = embedding[tokens].astype(sp.dtype)
        # A position is live up to the last target that counts in its
        # sequence.  Mixers are causal and padding is at the tail, so nothing
        # the loss reads depends on the positions after it: the routed layers
        # give them no rows (loss and gradients are exactly what they were).
        counted = targets != IGNORE
        live = jnp.flip(jnp.cumsum(jnp.flip(counted, 1), 1), 1) > 0
        held, largest, mean, fallbacks = 0.0, 0.0, 0.0, 0.0
        for i, block in enumerate(sp.blocks):
            x, rows = Block(sp, block, name=f"layer_{i}")(x, live)
            if rows is not None:
                held = held + rows["rows_held"]
                largest = largest + rows["rows_max"]
                mean = mean + rows["rows_mean"]
                fallbacks = fallbacks + rows["fallback"]
        x = RMSNorm(sp.norm_eps, sp.dtype, name="final_norm")(x)
        if not sp.tie_word_embeddings:
            embedding = Leaf("embedding", (sp.vocab_held, sp.hidden_size),
                             nn.initializers.normal(0.02), name="lm_head")()
        head = embedding.astype(sp.dtype)
        with jax.named_scope("lm_loss"):
            out = {"loss": next_token_loss(x, head, targets)}
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
