"""Mixture-of-Experts feed-forward with expert parallelism.

Beyond reference parity (SURVEY.md §2.6: "Expert parallelism (EP/MoE): No").
The reference's block FFN is a single square Dense (`transformer.py:126,140`);
this module is the opt-in MoE replacement: Switch-style top-1 routing
(Fedus et al. 2021) with a fixed per-expert capacity so every shape is static
under jit.

TPU-first formulation — dense dispatch, no gather/scatter:

  gates    = softmax(x @ w_gate)                  (tokens, E)
  dispatch = one_hot(top1) · within-capacity mask  (tokens, E, C)
  buffers  = einsum('te c, td -> e c d')           (E, C, d)  ← all-to-all
  expert   = gelu(buffers @ wi) @ wo               batched over E on the MXU
  out      = einsum('tec, ecd -> td')              combine, gate-weighted

Expert parallelism is pure sharding: the stacked expert weights (E, d, ff)
are partitioned over the mesh's ``model`` axis (rt1_tpu/parallel/sharding.py
`moe_parameter_rules`), and GSPMD lowers the dispatch/combine einsums to
all-to-alls over ICI. With a size-1 axis everything runs locally — same
program, no collectives. 8-device ≡ 1-device parity is pinned by
tests/test_moe.py.

Dropped-token semantics: tokens over an expert's capacity fall through the
residual connection untouched (combine weight 0) — standard Switch behavior.
An auxiliary load-balancing loss (`aux_loss`, Switch eq. 4) is returned for
the trainer to add.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


class MoEFeedForward(nn.Module):
    """Top-1 routed expert FFN: d_model → ff_dim (gelu) → d_model."""

    d_model: int
    num_experts: int = 4
    ff_dim: Optional[int] = None           # default: d_model (reference shape)
    capacity_factor: float = 2.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """x: (b, s, d) → (out (b, s, d), aux_loss scalar)."""
        b, s, d = x.shape
        e = self.num_experts
        ff = self.ff_dim or self.d_model
        t = b * s
        # Router in fp32: tiny, and routing decisions shouldn't flip under
        # bf16 rounding between two near-equal gate logits.
        tokens = x.reshape(t, d)
        gate_logits = nn.Dense(
            e, use_bias=False, dtype=jnp.float32, name="gate"
        )(tokens.astype(jnp.float32))
        gates = jax.nn.softmax(gate_logits, axis=-1)          # (t, e)
        expert_idx = jnp.argmax(gates, axis=-1)               # (t,)
        expert_gate = jnp.max(gates, axis=-1)                 # (t,)

        # Switch aux loss: E * Σ_e (fraction routed to e) · (mean gate to e).
        one_hot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # (t, e)
        density = one_hot.mean(axis=0)
        density_proxy = gates.mean(axis=0)
        aux_loss = (density * density_proxy).sum() * e

        # Position of each token within its expert's queue. NOTE: `t` is the
        # *call's* token count — under data parallelism this is the global
        # batch, so per-device expert buffers (E, C, d) grow with DP width
        # (they are sharded over 'model', not 'data'). For very large global
        # batches, lower capacity_factor or wrap the MoE in a shard_map over
        # 'data' so capacity binds per data shard.
        capacity = int(self.capacity_factor * t / e) or 1
        position_in_expert = (jnp.cumsum(one_hot, axis=0) - 1.0) * one_hot
        pos_one_hot = jax.nn.one_hot(   # (t, c); out-of-range (≥ capacity)
            position_in_expert.sum(axis=-1).astype(jnp.int32),  # exact: counts
            capacity, dtype=jnp.float32,
        )                               # rows are all-zero → token dropped
        dispatch = one_hot[:, :, None] * pos_one_hot[:, None, :]  # (t, e, c)

        # batch_axis=0: the leading expert axis is independent replicas, not
        # a receptive-field dim — plain lecun_normal would count fan_in as
        # E·d and under-scale every expert by ~sqrt(E) (Switch init recipe).
        expert_init = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", batch_axis=(0,)
        )
        # maybe_dequantize: the int8 serving path (models/quant.py) — a
        # no-op on f32/bf16 trees; the fp32 router above is never
        # quantized (parallel/plan.py rt1_quant_rules).
        from rt1_tpu.models.quant import maybe_dequantize

        wi = maybe_dequantize(
            self, self.param("wi", expert_init, (e, d, ff), jnp.float32),
            "wi_scale",
        ).astype(self.dtype)
        wo = maybe_dequantize(
            self, self.param("wo", expert_init, (e, ff, d), jnp.float32),
            "wo_scale",
        ).astype(self.dtype)

        dispatch = dispatch.astype(self.dtype)
        buffers = jnp.einsum("tec,td->ecd", dispatch, tokens.astype(self.dtype))
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buffers, wi))
        expert_out = jnp.einsum("ecf,efd->ecd", h, wo)         # (e, c, d)

        combine = dispatch * expert_gate.astype(self.dtype)[:, None, None]
        out = jnp.einsum("tec,ecd->td", combine, expert_out)
        return out.reshape(b, s, d), aux_loss.astype(jnp.float32)
