"""Fixture-only per-layer metric: batches served in the window."""


def read(run, trace):
    return run.get("batches") or None
