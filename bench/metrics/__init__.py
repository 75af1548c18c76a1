"""One reader per per-layer metric: ``read(rec)`` returns the metric's
value from the run record, or None when it finds nothing to read."""
