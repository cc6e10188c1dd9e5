"""The benchmark of rt1_tpu: harness, yardsticks and data (see PERF.md)."""
