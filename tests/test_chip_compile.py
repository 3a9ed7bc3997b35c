"""Compile the serving path for a TPU v5e at the chip smoke's widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and refuses here what it would refuse on the chip (an op that
does not lower, a program that does not fit).  Shapes are those of
``chip_smoke.py``: n = 1,000,000 rows of dim 128, Q = 64, L = 8, the
smoke's probes and candidate rungs.  Code that asks ``jax.default_backend()``
sees the CPU here, so each compile steers the executor choice to the one
the chip makes.  All compiles stay in this one file: only the process that
describes the topology may load the TPU library.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as jax_cc
from jax.sharding import SingleDeviceSharding

from repro.core import pipeline as pipe
from repro.core.index import build_index, make_params, probe_index
from repro.core.segments import _finish_segment, _query_delta
from repro.kernels import ops as kops

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke as smoke  # noqa: E402

SHAPE = smoke.SIFT1M
CFG = smoke.index_config(SHAPE)
WORST_RUNG = CFG.num_tables * CFG.probes_per_table * CFG.candidate_cap
TOMBSTONES = 1 << (SHAPE.deletes - 1).bit_length()
HBM_BYTES = 16 * 2 ** 30           # one v5e chip
EXECUTORS = kops.executors         # the real table, before any steering


def test_executor_choice_per_backend():
    # the chip gets the executors these compiles prove; off the chip the
    # merge runs the (interpreted) Pallas kernel the parity tests pin
    assert EXECUTORS("tpu") == {"probe": "xla", "rerank": "xla",
                                "merge": "xla"}
    assert EXECUTORS("cpu")["merge"] == "pallas"
    assert EXECUTORS()["merge"] == "pallas"   # this suite runs on the CPU


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_chip(monkeypatch, one_chip):
    """Executors as the chip picks them, and no persistent cache (a compile
    for a described chip is written to it but cannot be read back)."""
    monkeypatch.setattr(kops, "executors",
                        lambda backend=None: EXECUTORS("tpu"))
    monkeypatch.setattr(kops, "use_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    jax_cc.reset_cache()
    yield one_chip
    jax.config.update("jax_enable_compilation_cache", True)
    jax_cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _spec_of(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state(chip):
    data = jax.ShapeDtypeStruct((SHAPE.n, SHAPE.dim), jnp.int32)
    return _on(chip, jax.eval_shape(
        lambda d: build_index(CFG, jax.random.PRNGKey(0), d), data))


def _queries(chip):
    return _spec_of((SHAPE.batch, SHAPE.dim), jnp.int32, chip)


def _compile(fn, *args):
    """Compile for the described chip; the program must fit its HBM."""
    mem = fn.lower(*args).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


def test_compile_build_hash_and_sort(as_chip):
    params = make_params(CFG, jax.random.PRNGKey(0), SHAPE.dim)
    build = jax.jit(lambda d, p: build_index(CFG, None, d, params=p))
    _compile(build, _spec_of((SHAPE.n, SHAPE.dim), jnp.int32, as_chip),
             _on(as_chip, params))


def test_compile_phase_a_probe_extents(as_chip):
    _compile(probe_index, CFG, _state(as_chip), _queries(as_chip))


@pytest.mark.parametrize("rung", [smoke.CAND_BUCKET_MIN, WORST_RUNG])
def test_compile_phase_b_gather_tombstone_rerank(as_chip, rung):
    state, q = _state(as_chip), _queries(as_chip)
    probe_keys, lo, occ, _ = _on(as_chip, jax.eval_shape(
        lambda s, x: probe_index(CFG, s, x), state, q))
    _compile(_finish_segment, CFG, rung, None, state,
             _spec_of((SHAPE.n,), jnp.int32, as_chip),
             _spec_of((TOMBSTONES,), jnp.int32, as_chip),
             probe_keys, lo, occ, q)


def test_compile_delta_scan(as_chip):
    cap = SHAPE.delta_cap
    _compile(_query_delta, CFG,
             _spec_of((cap, SHAPE.dim), jnp.int32, as_chip),
             _spec_of((cap,), jnp.int32, as_chip),
             _spec_of((), jnp.int32, as_chip),
             _spec_of((TOMBSTONES,), jnp.int32, as_chip), _queries(as_chip))


def test_compile_merge(as_chip):
    top = _spec_of((SHAPE.batch, CFG.k), jnp.int32, as_chip)
    _compile(jax.jit(pipe.stage_merge_pair), top, top, top, top)
