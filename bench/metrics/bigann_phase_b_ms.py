"""``phase_b_ms`` in the bigann-10M cell: phase B's device time per served
batch, by the same reader, under a name of its own."""
from bench.metrics.phase_b_ms import read  # noqa: F401
