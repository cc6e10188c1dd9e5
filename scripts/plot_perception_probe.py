"""Figure for the round-5 perception-capacity probe results.

Two panels from artifacts/perception_probe_r05.json (written by
scripts/perception_probe.py):
  left  — attainable val position RMSE per (encoder, resolution) arm
          (magnitude of one measure → single-hue bars, direct labels);
  right — val RMSE vs pretraining step per arm (categorical hues in fixed
          slot order, direct labels + legend).

Usage:
  JAX_PLATFORMS=cpu \
    python scripts/plot_perception_probe.py
"""

import json
import os
import sys

import matplotlib

matplotlib.use("Agg")
import matplotlib.pyplot as plt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Categorical slots 1-3 (fixed order) + text/surface tokens from the
# dataviz reference palette (pre-validated CVD-safe set).
SERIES = ["#2a78d6", "#eb6834", "#1baf7a", "#eda100", "#e87ba4"]
TEXT = "#0b0b0b"
TEXT2 = "#52514e"
SURFACE = "#fcfcfb"
GRID = "#e4e3df"

BLOCK_MM = 30.0  # Language-Table block side, for the reference line


def main():
    path = os.path.join(REPO, "artifacts", "perception_probe_r05.json")
    results_path = "/root/perception_probe/probe_results.json"
    data = None
    for p in (results_path, path):
        if os.path.exists(p):
            with open(p) as f:
                data = json.load(f)
            break
    if not data:
        sys.exit(f"no probe results at {results_path} or {path}")

    arms = list(data.keys())
    fig, (ax1, ax2) = plt.subplots(
        1, 2, figsize=(10, 4), facecolor=SURFACE,
        gridspec_kw={"width_ratios": [1, 1.4]},
    )
    for ax in (ax1, ax2):
        ax.set_facecolor(SURFACE)
        for s in ("top", "right"):
            ax.spines[s].set_visible(False)
        for s in ("left", "bottom"):
            ax.spines[s].set_color(GRID)
        ax.tick_params(colors=TEXT2, labelsize=9)

    # Left: RMSE floor per arm — one measure, one hue, direct labels.
    rmses = [data[a]["val_rmse_mm"] for a in arms]
    y = range(len(arms))
    ax1.barh(y, rmses, height=0.55, color=SERIES[0], zorder=3)
    ax1.set_yticks(list(y))
    ax1.set_yticklabels(
        [a.replace("_", " @ ") for a in arms], color=TEXT, fontsize=9
    )
    ax1.invert_yaxis()
    for i, v in enumerate(rmses):
        ax1.text(v + 0.6, i, f"{v:.1f}", va="center", fontsize=9,
                 color=TEXT)
    ax1.axvline(BLOCK_MM, color=TEXT2, lw=1, ls=":", zorder=2)
    ax1.text(BLOCK_MM, -0.55, "block width", fontsize=8, color=TEXT2,
             ha="center")
    ax1.set_xlabel("val position RMSE (mm) — lower is better", color=TEXT2,
                   fontsize=9)
    ax1.xaxis.grid(True, color=GRID, lw=0.6, zorder=0)
    ax1.set_axisbelow(True)

    # Right: training histories — categorical hues, direct end labels.
    for i, a in enumerate(arms):
        hist = data[a].get("history", [])
        if not hist:
            continue
        xs = [h["step"] for h in hist]
        ys = [h["val_rmse"] * 1000 for h in hist]
        ax2.plot(xs, ys, color=SERIES[i % len(SERIES)], lw=2,
                 label=a.replace("_", " @ "), zorder=3)
        ax2.annotate(
            f'{ys[-1]:.0f}', (xs[-1], ys[-1]), textcoords="offset points",
            xytext=(4, 0), fontsize=8, color=TEXT,
        )
    ax2.set_xlabel("pretraining step", color=TEXT2, fontsize=9)
    ax2.set_ylabel("val RMSE (mm)", color=TEXT2, fontsize=9)
    ax2.yaxis.grid(True, color=GRID, lw=0.6, zorder=0)
    ax2.set_axisbelow(True)
    leg = ax2.legend(frameon=False, fontsize=9)
    for t in leg.get_texts():
        t.set_color(TEXT)

    fig.suptitle(
        "Perception capacity, measured directly: block/effector position "
        "regression from sim frames",
        fontsize=11, color=TEXT, y=1.0,
    )
    fig.tight_layout()
    out = os.path.join(REPO, "artifacts", "perception_probe_r05.png")
    fig.savefig(out, dpi=130, bbox_inches="tight", facecolor=SURFACE)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
