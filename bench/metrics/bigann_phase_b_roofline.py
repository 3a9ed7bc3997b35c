"""``phase_b_roofline`` in the bigann-10M cell, by the same reader: the
rows count one byte a coordinate (``itemsize`` 1 from the configuration's
uint8 ``dataset_dtype``, through ``phase_b_bytes``)."""
from bench.metrics.phase_b_roofline import read  # noqa: F401
