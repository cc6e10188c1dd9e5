"""Collect a tiny oracle dataset and iterate training batches.

Parity source: reference `language_table/examples/dataset_example.py:37-53`
(TFDS iteration). Ours generates its own data with the scripted RRT oracle
(no external dataset needed) and feeds it through the windowed pipeline.

Run: JAX_PLATFORMS=cpu python examples/dataset_example.py
"""

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import glob
import tempfile

from rt1_tpu.data.collect import collect_dataset
from rt1_tpu.data.pipeline import WindowedEpisodeDataset
from rt1_tpu.envs import blocks


def main():
    data_dir = os.path.join(tempfile.gettempdir(), "lt_example_data")
    if not glob.glob(os.path.join(data_dir, "train", "episode_*.npz")):
        print("collecting 4 oracle episodes...")
        collect_dataset(
            data_dir,
            4,
            block_mode=blocks.BlockMode.BLOCK_4,
            seed=0,
            max_steps=120,
            image_hw=(90, 160),
            splits=(("train", 1.0),),
        )

    paths = sorted(glob.glob(os.path.join(data_dir, "train", "episode_*.npz")))
    ds = WindowedEpisodeDataset(
        paths, window=6, crop_factor=0.95, height=128, width=228
    )
    print(f"{len(paths)} episodes, {len(ds)} windows")

    batches = ds.numpy_batches(batch_size=4, num_epochs=1)
    batch = next(batches)
    for group, tree in batch.items():
        for key, arr in tree.items():
            print(f"{group}/{key}: {arr.shape} {arr.dtype}")


if __name__ == "__main__":
    main()
