"""Per-layer metric readers: read(ctx, meta) -> float, or None where this
run holds nothing to read (the harness then leaves the metric out)."""
