"""One reader per per-layer metric, <metric name>.py with ``read(reading)``; found by name."""
