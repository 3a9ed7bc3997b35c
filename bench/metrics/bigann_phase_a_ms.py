"""``phase_a_ms`` in the bigann-10M cell: phase A's device time per served
batch, by the same reader, under a name of its own so that the cell's 10x
keys per table are not compared with SIFT1M's."""
from bench.metrics.phase_a_ms import read  # noqa: F401
