"""Closed-loop policy diagnostics: oracle-agreement, constancy, progress.

Round 2's copycat-BC diagnosis was assembled by hand; this
script makes it a one-command artifact. For each eval episode it rolls the
trained policy while querying the scripted RRT oracle *in parallel* on the
same states (the oracle acts as a per-step reference action, not as the
actor), and reports:

* **oracle agreement** — per-step cosine similarity between the policy's
  action and the oracle's planned action (the quantity BC actually tries to
  maximize; near-zero mean = the policy ignores the task).
* **constancy** — per-episode std of the policy's actions (the copycat
  collapse signature is a near-constant output, round-2 measured
  std ≈ 0.0004).
* **progress** — start-to-end change in block→target distance (did the
  policy move the right block toward the goal at all, even without
  reaching the sparse-reward threshold).

Run (CPU is fine):
  JAX_PLATFORMS=cpu python scripts/policy_diagnostics.py \
      --workdir /root/learn_proof_t1 --seq_len 1 \
      --image_tokenizer efficientnet_small --dtype float32 \
      --height 64 --width 96 --diag_episodes 10
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np
from absl import app, flags

import learn_proof  # noqa: E402  (registers its flags: --workdir etc.)

FLAGS = flags.FLAGS
# learn_proof already owns --episodes (collection count); diagnostics get
# their own names.
# >=20 by default: the round-3 6-episode diagnostics had enough variance to
# fake a regression at ck15000 (VERDICT r3 weak #4).
flags.DEFINE_integer("diag_episodes", 20, "Diagnostic episodes.")
flags.DEFINE_integer("max_steps", 80, "Step budget per episode.")
flags.DEFINE_integer("diag_seed", 20_000, "Env seed (disjoint from train/eval).")
flags.DEFINE_string("out", "", "Output JSON (default: <workdir>/diagnostics.json)")
flags.DEFINE_bool(
    "corpus_entropy", False,
    "Compute the corpus' marginal action-token entropy (the token-CE "
    "plateau bar, round-3 diagnosis) instead of closed-loop "
    "diagnostics. Needs only <workdir>/data, no checkpoint.")
flags.DEFINE_integer(
    "entropy_episodes", 200, "Train episodes to scan for --corpus_entropy.")


def corpus_entropy(data_dir, n_episodes, vocab_size=256):
    """Marginal token entropy of the demo corpus, in nats per action token.

    A policy that fits only the marginal action distribution (ignoring
    observations) plateaus at this cross-entropy; a val CE above it means
    the model hasn't even matched the marginal, and CE below it is the
    first evidence of input-dependence. Exact for T=1; for T>1 the bar is
    approximate — windowing pads each episode's first window-1 positions by
    repeating step 0 (pipeline.py), reweighting the label marginal slightly.
    The `displayed_loss_at` entries convert to the reference loss scaling
    (raw mean-per-token CE divided by b*t*(I+A),
    transformer_network.py:314-319) for this repo's standard arm configs,
    assuming the flagship 8 image tokens (I+A=11).
    """
    import glob

    from rt1_tpu.data.episodes import load_episode, read_reference_episode
    from rt1_tpu.models.action_tokenizer import tokenize
    from rt1_tpu.specs import language_table_action_space

    space = language_table_action_space()
    paths = sorted(glob.glob(os.path.join(data_dir, "train", "episode_*.np*")))
    if not paths:
        raise FileNotFoundError(f"no train episodes under {data_dir}")
    paths = paths[:n_episodes]
    counts = None
    for path in paths:
        ep = (
            read_reference_episode(path)
            if path.endswith(".npy")
            else load_episode(path)
        )
        actions = np.asarray(ep["action"], np.float32)  # (T, 2)
        tokens = np.asarray(
            tokenize(
                space,
                {
                    "terminate_episode": np.asarray(
                        ep["is_terminal"], np.int32
                    ),
                    "action": actions,
                },
                vocab_size,
            )
        )  # (T, A)
        if counts is None:
            counts = np.zeros((tokens.shape[-1], vocab_size), np.int64)
        for pos in range(tokens.shape[-1]):
            counts[pos] += np.bincount(tokens[:, pos], minlength=vocab_size)

    def entropy(c):
        p = c / c.sum()
        nz = p[p > 0]
        return float(-(nz * np.log(nz)).sum())

    per_token = [entropy(c) for c in counts]
    mean_nats = float(np.mean(per_token))
    tokens_per_step = 11  # flagship: I=8 image + A=3 action tokens
    return {
        "episodes_scanned": len(paths),
        "per_token_entropy_nats": per_token,
        "mean_entropy_nats": mean_nats,
        "displayed_loss_assumes": "8 image tokens (I+A=11); T>1 bars are "
                                  "approximate (first-frame window padding "
                                  "reweights the label marginal)",
        "displayed_loss_at": {
            f"b{b}_T{t}": mean_nats / (b * t * tokens_per_step)
            for b, t in ((32, 1), (32, 6), (16, 1), (8, 6))
        },
    }


def main(argv):
    del argv
    data_dir = os.path.join(FLAGS.workdir, "data")
    train_dir = os.path.join(FLAGS.workdir, "train")
    if FLAGS.corpus_entropy:
        report = corpus_entropy(data_dir, FLAGS.entropy_episodes)
        out = FLAGS.out or os.path.join(FLAGS.workdir, "corpus_entropy.json")
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
        print(json.dumps(report, indent=2))
        return

    from rt1_tpu.envs import blocks
    from rt1_tpu.envs.oracles import RRTPushOracle
    from rt1_tpu.eval.evaluate import build_eval_env

    learn_proof._check_train_meta(train_dir, "diagnostics",
                                  learn_proof.EVAL_META_KEYS)
    policy = learn_proof._restore_policy(train_dir, data_dir)

    env = build_eval_env(
        reward_name=learn_proof.REWARD,
        block_mode=blocks.BlockMode(FLAGS.block_mode),
        seed=FLAGS.diag_seed,
        embedder=FLAGS.embedder,
        target_height=FLAGS.height,
        target_width=FLAGS.width,
        sequence_length=FLAGS.seq_len,
    )

    episodes = []
    for ep in range(FLAGS.diag_episodes):
        oracle = RRTPushOracle(env, use_ee_planner=True)
        while True:
            obs = env.reset()
            if oracle.get_plan(env.compute_state()):
                break
        policy.reset()
        d0 = _block_target_distance(env)
        cos, acts = [], []
        done, steps = False, 0
        while not done and steps < FLAGS.max_steps:
            a_pi = np.asarray(policy.action(obs), np.float64)
            a_star = np.asarray(
                oracle.action(env.compute_state()), np.float64
            )[:2]
            na, nb = np.linalg.norm(a_pi), np.linalg.norm(a_star)
            if na > 1e-9 and nb > 1e-9:
                cos.append(float(a_pi @ a_star / (na * nb)))
            acts.append(a_pi)
            obs, _, done, _ = env.step(a_pi.astype(np.float32))
            steps += 1
        acts = np.asarray(acts)
        episodes.append({
            "success": bool(env.succeeded),
            "steps": steps,
            "oracle_cosine_mean": float(np.mean(cos)) if cos else None,
            "action_std": float(np.mean(np.std(acts, axis=0))),
            "action_abs_p50": float(np.median(np.abs(acts))),
            "block_target_dist_start": d0,
            "block_target_dist_end": _block_target_distance(env),
        })
        print(f"ep {ep}: {episodes[-1]}")

    cos_means = [e["oracle_cosine_mean"] for e in episodes
                 if e["oracle_cosine_mean"] is not None]
    deltas = [e["block_target_dist_start"] - e["block_target_dist_end"]
              for e in episodes
              if e["block_target_dist_start"] is not None
              and e["block_target_dist_end"] is not None]
    summary = {
        "episodes": FLAGS.diag_episodes,
        "successes": sum(e["success"] for e in episodes),
        "oracle_cosine_mean": float(np.mean(cos_means)) if cos_means else None,
        "action_std_mean": float(np.mean([e["action_std"] for e in episodes])),
        "block_target_progress_mean": float(np.mean(deltas)) if deltas else None,
        "per_episode": episodes,
    }
    out = FLAGS.out or os.path.join(FLAGS.workdir, "diagnostics.json")
    with open(out, "w") as f:
        json.dump(summary, f, indent=2)
    print(json.dumps({k: v for k, v in summary.items()
                      if k != "per_episode"}, indent=2))


def _block_target_distance(env):
    """Start-block → target-block distance for the current block2block task.

    Wrapper chain passes attribute access through (`EnvWrapper.__getattr__`),
    so `_reward_calculator` and `compute_state` resolve on the base env; the
    state dict carries per-block `block_<name>_translation` entries
    (`rt1_tpu/envs/language_table.py::_compute_state`).
    """
    try:
        reward = env._reward_calculator
        state = env.compute_state(request_task_update=False)
        start = np.asarray(
            state[f"block_{reward._start_block}_translation"], np.float64
        )
        target = np.asarray(
            state[f"block_{reward._target_block}_translation"], np.float64
        )
        return float(np.linalg.norm(start - target))
    except Exception:
        return None  # keep the JSON well-formed on non-block2block tasks


if __name__ == "__main__":
    app.run(main)
