"""``rung_mean`` in the bigann-10M cell: the mean candidate rung of the
window's batches, by the same reader, under a name of its own."""
from bench.metrics.rung_mean import read  # noqa: F401
