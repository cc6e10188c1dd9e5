"""Perception-capacity probe + encoder pretraining (VERDICT r4 next #3).

Round 4 concluded "at efficientnet_small/64x96 the policy decorrelates
rather than aligns — a perception-capacity limit" from a single
(capacity, resolution) point, with from-scratch vision as a confound.
This driver measures the confound directly:

* For each (width/depth coefficient, resolution) arm, pretrain the exact
  RT-1 tokenizer encoder on block/effector state regression from rendered
  sim frames (labels are free) and record the attainable position RMSE —
  perception capacity measured independent of BC/DAgger dynamics.
* Save each arm's encoder (rt1_tpu/train/pretrain_vision.py::save_encoder)
  so the winning one seeds a BC arm via `learn_proof.py
  --pretrained_encoder` — the initialization half of the question.

Run (CPU, chip-independent):
  JAX_PLATFORMS=cpu python scripts/perception_probe.py \
      --out_dir /root/perception_probe --frames 12000 --steps 3000
"""

import argparse
import json
import os
import sys
import time

# Line-buffer stdout so detached runs show live progress in their log.
sys.stdout.reconfigure(line_buffering=True)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (name, width/depth coefficients, (H, W)). small@64x96 is the round-4 arm
# config (the baseline point); the others vary resolution and width one
# axis at a time.
ARMS = [
    ("small_64x96", 0.35, 0.35, (64, 96)),
    ("small_96x160", 0.35, 0.35, (96, 160)),
    ("wide_64x96", 0.70, 0.35, (64, 96)),
    ("small_128x224", 0.35, 0.35, (128, 224)),
    # Flagship coefficients (B3). CPU-expensive: select explicitly via
    # --arms (pretraining this one is chip-class work; the graft then
    # seeds the flagship learn_proof arm via --pretrained_encoder).
    ("b3_128x224", 1.2, 1.4, (128, 224)),
]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--out_dir", default="/root/perception_probe")
    p.add_argument("--frames", type=int, default=12000)
    p.add_argument("--steps", type=int, default=3000)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--arms", default="",
                   help="comma-separated arm names; empty = all")
    args = p.parse_args()

    from rt1_tpu.train.pretrain_vision import (
        generate_state_regression_dataset,
        pretrain_encoder,
        save_encoder,
    )

    os.makedirs(args.out_dir, exist_ok=True)
    selected = [a for a in ARMS
                if not args.arms or a[0] in args.arms.split(",")]
    results_path = os.path.join(args.out_dir, "probe_results.json")
    results = {}
    if os.path.exists(results_path):
        with open(results_path) as f:
            results = json.load(f)

    # One dataset per resolution, generated at the LARGEST needed size and
    # reused (cv2 downsizing from native happens per-arm inside generation
    # — regenerate per resolution to keep each arm's pipeline identical to
    # what training sees).
    for name, wc, dc, hw in selected:
        if name in results:
            print(f"[probe] {name}: already recorded, skipping")
            continue
        t0 = time.time()
        print(f"[probe] {name}: generating {args.frames} frames @ {hw}")
        images, targets, target_names = generate_state_regression_dataset(
            args.frames, seed=args.seed, image_hw=hw,
        )
        gen_s = time.time() - t0
        print(f"[probe] {name}: dataset in {gen_s:.0f}s; training "
              f"{args.steps} steps")
        t1 = time.time()
        variables, metrics = pretrain_encoder(
            images, targets,
            num_steps=args.steps, batch_size=args.batch, seed=args.seed,
            width_coefficient=wc, depth_coefficient=dc,
        )
        enc_path = os.path.join(args.out_dir, f"encoder_{name}.msgpack")
        save_encoder(variables, metrics, enc_path)
        results[name] = {
            "width_coefficient": wc,
            "depth_coefficient": dc,
            "resolution": list(hw),
            "frames": args.frames,
            "steps": args.steps,
            "val_rmse_mm": metrics["val_rmse_mm"],
            "history": metrics["history"],
            "target_names": target_names,
            "dataset_seconds": round(gen_s, 1),
            "train_seconds": round(time.time() - t1, 1),
            "encoder_path": enc_path,
        }
        with open(results_path + ".tmp", "w") as f:
            json.dump(results, f, indent=2)
        os.replace(results_path + ".tmp", results_path)
        print(f"[probe] {name}: val position RMSE "
              f"{metrics['val_rmse_mm']:.2f} mm "
              f"({time.time() - t0:.0f}s total)")

    # Committable summary artifact.
    summary = {
        name: {k: v for k, v in r.items() if k != "history"}
        for name, r in results.items()
    }
    art = os.path.join(REPO, "artifacts", "perception_probe_r05.json")
    with open(art, "w") as f:
        json.dump(summary, f, indent=2)
    print(f"[probe] summary -> {art}")


if __name__ == "__main__":
    main()
