"""The trace reduction: xplane to rows, rows to numbers, and the traced slice."""
