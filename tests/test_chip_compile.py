"""Compile the serving path for a TPU v5e at the chip smoke's widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and refuses here what it would refuse on the chip (an op that
does not lower, a program that does not fit).  Shapes are those of
``chip_smoke.py``: n = 1,000,000 rows of dim 128, Q = 64, L = 8, the
smoke's probes and candidate rungs; the build and phase B also at the
``bigann10m-u8`` benchmark configuration's n = 10,000,000 uint8 rows.
Every query stage has one executor on every backend, so what compiles here
is what the chip runs.  All compiles stay in this one file: only the
process that describes the topology may load the TPU library.
"""
import dataclasses
import json
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

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke as smoke  # noqa: E402

SHAPE = smoke.SIFT1M
CFG = smoke.index_config(SHAPE)
WORST_RUNG = CFG.num_tables * CFG.probes_per_table * CFG.candidate_cap


def _bigann():
    """(shape, index config) of the ``bigann10m-u8`` benchmark cell."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "configs", "bigann10m-u8.json")
    with open(path) as f:
        conf = json.load(f)
    law = conf["data"]
    shape = dataclasses.replace(SHAPE, n=law["n"], dim=law["dim"],
                                universe=law["universe"])
    return shape, smoke.index_config(shape, **conf["index"])


BIGANN, BIGANN_CFG = _bigann()
BIGANN_WORST_RUNG = (BIGANN_CFG.num_tables * BIGANN_CFG.probes_per_table
                     * BIGANN_CFG.candidate_cap)
# (shape, index config): SIFT1M's int32 rows, bigann-10M's uint8 rows
STORES = {"1M-int32": (SHAPE, CFG), "10M-uint8": (BIGANN, BIGANN_CFG)}
TOMBSTONES = 1 << (SHAPE.deletes - 1).bit_length()
HBM_BYTES = 16 * 2 ** 30           # one v5e chip


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
def as_chip(one_chip):
    """The described chip, and no persistent cache (a compile for a
    described chip is written to it but cannot be read back)."""
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


def _state(chip, shape=SHAPE, cfg=CFG):
    data = jax.ShapeDtypeStruct((shape.n, shape.dim), jnp.int32)
    return _on(chip, jax.eval_shape(
        lambda d: build_index(cfg, jax.random.PRNGKey(0), d), data))


def _queries(chip, shape=SHAPE):
    return _spec_of((shape.batch, shape.dim), jnp.int32, chip)


def _compile(fn, *args):
    """Compile for the described chip; the program must fit its HBM."""
    mem = fn.lower(*args).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("store", sorted(STORES))
def test_compile_build_hash_and_sort(as_chip, store):
    """The build as one program, its rows hashed in blocks: at 10M rows
    the int32 input, the index and the block's temporaries fit."""
    shape, cfg = STORES[store]
    params = make_params(cfg, jax.random.PRNGKey(0), shape.dim)
    build = jax.jit(lambda d, p: build_index(cfg, None, d, params=p))
    _compile(build, _spec_of((shape.n, shape.dim), jnp.int32, as_chip),
             _on(as_chip, params))


def test_compile_phase_a_probe_extents(as_chip):
    _compile(probe_index, CFG, _state(as_chip), _queries(as_chip))


def _phase_b_args(chip, shape=SHAPE, cfg=CFG, rung=WORST_RUNG):
    """``_finish_segment``'s arguments for one batch at ``rung``."""
    state, q = _state(chip, shape, cfg), _queries(chip, shape)
    probe_keys, lo, occ, _ = _on(chip, jax.eval_shape(
        lambda s, x: probe_index(cfg, s, x), state, q))
    return (cfg, rung, None, state, _spec_of((shape.n,), jnp.int32, chip),
            _spec_of((TOMBSTONES,), jnp.int32, chip), probe_keys, lo, occ, q)


@pytest.mark.parametrize("phase, module", [
    ("a", "jit_probe_index"), ("b", "jit__finish_segment")])
def test_query_programs_keep_their_trace_names(as_chip, phase, module):
    """The two programs a served batch runs carry the module names the
    benchmark's per-layer metrics read from a profiler trace."""
    if phase == "a":
        lowered = probe_index.lower(CFG, _state(as_chip), _queries(as_chip))
    else:
        lowered = _finish_segment.lower(*_phase_b_args(as_chip))
    head = lowered.as_text().split("\n", 1)[0]
    assert head.startswith(f"module @{module} "), head


@pytest.mark.parametrize("store, rung", [
    ("1M-int32", smoke.CAND_BUCKET_MIN), ("1M-int32", WORST_RUNG),
    ("10M-uint8", BIGANN_WORST_RUNG)])
def test_compile_phase_b_gather_tombstone_rerank(as_chip, store, rung):
    shape, cfg = STORES[store]
    _compile(_finish_segment, *_phase_b_args(as_chip, shape, cfg, rung))


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
