"""Optimizer & LR schedule parity with the reference.

Reference (`distribute_train.py:99-110`): `torch.optim.Adam(lr=args.lr)` (5e-4,
`:278`) + `MultiStepLR(milestones=[50, 75, 90], gamma=0.1)` stepped **per epoch**.
Here the schedule is expressed in optimizer steps (JAX schedules are step-indexed);
`multistep_lr` converts epoch milestones given steps-per-epoch.

Torch-Adam vs optax note: `optax.adam` defaults (b1=0.9, b2=0.999, eps=1e-8) match
`torch.optim.Adam` defaults, and optax's eps is applied like torch's (outside the
bias-corrected sqrt — `optax.scale_by_adam` uses eps_root=0 for the sqrt), so the
update rule is numerically equivalent.
"""

from __future__ import annotations

from typing import Optional, Sequence

import optax

from rt1_tpu.obs import startup


def multistep_lr(
    base_lr: float,
    milestones: Sequence[int],
    gamma: float = 0.1,
    steps_per_epoch: int = 1,
) -> optax.Schedule:
    """torch `MultiStepLR` as an optax schedule (milestones in epochs)."""
    boundaries = {int(m) * steps_per_epoch: gamma for m in milestones}
    return optax.piecewise_constant_schedule(base_lr, boundaries)


@startup.phased("make_optimizer")
def make_optimizer(
    learning_rate: float = 5e-4,
    milestones: Sequence[int] = (50, 75, 90),
    gamma: float = 0.1,
    steps_per_epoch: int = 1,
    grad_clip_norm: Optional[float] = None,
    weight_decay: float = 0.0,
) -> optax.GradientTransformation:
    """Adam + MultiStepLR, with optional extras the reference lacks (clip, wd)."""
    schedule = multistep_lr(learning_rate, milestones, gamma, steps_per_epoch)
    parts = []
    if grad_clip_norm is not None:
        parts.append(optax.clip_by_global_norm(grad_clip_norm))
    if weight_decay:
        parts.append(optax.add_decayed_weights(weight_decay))
    parts.append(optax.adam(schedule))
    return optax.chain(*parts) if len(parts) > 1 else parts[0]
