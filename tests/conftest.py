import os
import sys
import tempfile
import types

# Smoke tests and benches must see ONE device; only launch/dryrun.py (run as
# its own process) forces 512 placeholder devices.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# One fixed persistent compile cache outside the checkout (under the temp
# dir), set before jax is imported: test runs then never grow the tree,
# and the engine sets no cache directory of its own.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "repro-jax-cache"))

import numpy as np
import pytest

# ---------------------------------------------------------------------------
# Graceful degradation on bare machines: `hypothesis` is a dev-only extra
# (requirements-dev.txt).  When it is missing, install an importorskip-style
# shim so the property-test modules still *collect*; every @given test then
# skips cleanly instead of erroring the whole collection.
# ---------------------------------------------------------------------------
try:
    import hypothesis  # noqa: F401
except ImportError:
    class _Strategy:
        """Stand-in for hypothesis strategy objects: absorbs any chained
        call (st.integers(...).map(...), .filter(...), ...)."""

        def __call__(self, *args, **kwargs):
            return self

        def __getattr__(self, name):
            return self

    def _given(*_args, **_kwargs):
        def deco(fn):
            # No functools.wraps: pytest would follow __wrapped__ and
            # mistake the hypothesis-bound parameters for fixtures.
            def wrapper():
                pytest.skip("hypothesis not installed "
                            "(pip install -r requirements-dev.txt)")
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            return wrapper
        return deco

    def _settings(*_args, **_kwargs):
        return lambda fn: fn

    _stub = types.ModuleType("hypothesis")
    _stub.given = _given
    _stub.settings = _settings
    _stub.strategies = _Strategy()
    _stub.__is_repro_shim__ = True
    sys.modules["hypothesis"] = _stub


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


# ---------------------------------------------------------------------------
# Cap the suite's memory-mapping count.  Every jitted executable lives in
# jax's process-lifetime caches, and each one holds mmap'd code pages; a
# few hundred engine-heavy tests accumulate ~65k mappings, overrun the
# kernel's vm.max_map_count default (65530), and the next mmap inside XLA
# — a compile or a cache deserialize — segfaults the whole run.  Dropping
# the jit caches between modules keeps the count bounded; the persistent
# compilation cache (DESIGN.md §8) turns the resulting recompiles into
# disk reads.
# ---------------------------------------------------------------------------
@pytest.fixture(autouse=True, scope="module")
def _free_jit_executables():
    yield
    if "jax" in sys.modules:
        try:
            sys.modules["jax"].clear_caches()
        except Exception:
            pass
