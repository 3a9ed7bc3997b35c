"""A whole run of a cell on the CPU, past the harness's look for a chip:
sound, it is correct; with the timed path broken underneath, or with the
control in the program's place, it is not.

The cell is the fixture ``tiny.batch16``: the sift1m-l1 law and serving
path at 4,096 rows of 64 dims (U = 510, so L1 distances run past 512 and
bfloat16 has to round them, as it does at the real sizes).
"""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness  # noqa: E402


def _load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "bench", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def run(monkeypatch):
    """``bench/run.py`` pointed at the fixture cell, with no chip check."""
    import jax
    mod = _load_run()
    monkeypatch.setattr(mod, "SPEC", os.path.join(FIX, "BENCHMARK.json"))
    monkeypatch.setattr(mod, "DIRS", (FIX,))
    monkeypatch.setattr(mod, "chips_missing", lambda chips: None)
    # keep the test process's own compilation cache
    monkeypatch.setattr(mod, "CACHE",
                        jax.config.jax_compilation_cache_dir or "")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       jax.config.jax_compilation_cache_dir or "")

    def go(capsys, seed=2 ** 40 + 11, trace=0):
        rc = mod.main(["--workload", "tiny.batch16", "--seed", str(seed),
                       "--seconds", "0.5", "--trace", str(trace)])
        out = capsys.readouterr()
        assert rc == 0, out.err[-2000:]
        result = json.loads(out.out.strip().splitlines()[-1])
        assert list(result)[-1] == "checks"
        assert out.err.strip().splitlines()[-1].startswith("check ")
        return result

    return go


def test_sound_run_is_correct(run, capsys):
    result = run(capsys)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"queries_per_s", "recall_at_10",
                                      "setup_s"}
    assert result["device"]["count"] == 1


def _alter_one_answer(d, i):
    """A token altered where it is produced: one id moved to its
    neighbouring row, its distance left as it was."""
    return d, i.at[0, 0].set(i[0, 0] + 1)


def _drop_half_the_batch(d, i):
    """Half of the batch left out: the second half answered with the
    first half's results."""
    h = d.shape[0] // 2
    return d.at[h:2 * h].set(d[:h]), i.at[h:2 * h].set(i[:h])


@pytest.mark.parametrize("fault", ["altered_answer", "half_batch_dropped",
                                   "compile_in_window"])
def test_broken_timed_path_is_not_correct(run, capsys, monkeypatch, fault):
    from repro.core import segments
    from repro.serve import engine
    if fault == "compile_in_window":
        import jax
        serve = engine.AnnServingEngine.query_batch

        def serve_forgetting(self, queries):
            """Drops every compiled program after each batch, so the next
            one compiles (or loads) them again."""
            out = serve(self, queries)
            jax.clear_caches()
            return out

        monkeypatch.setattr(engine.AnnServingEngine, "query_batch",
                            serve_forgetting)
    else:
        broken = {"altered_answer": _alter_one_answer,
                  "half_batch_dropped": _drop_half_the_batch}[fault]
        finish = segments._finish_segment

        def finish_broken(*args):
            return broken(*finish(*args))

        monkeypatch.setattr(segments, "_finish_segment", finish_broken)
    result = run(capsys)
    assert not result["correct"], result["checks"]
    checks = result["checks"]
    failing = {name for name, c in checks.items()
               if ("at_least" in c and c["value"] < c["at_least"])
               or ("at_most" in c and c["value"] > c["at_most"])}
    expect = {"altered_answer": "wrong_answers",
              "half_batch_dropped": "wrong_answers",
              "compile_in_window": "window_compiles"}[fault]
    assert expect in failing


def test_control_in_the_programs_place_is_not_correct():
    """The control (``bench/control.py``: brute force with distances in
    bfloat16, answering the queries a run serves) has to be refused."""
    from bench import control
    spec = harness.load_spec(os.path.join(FIX, "BENCHMARK.json"))
    cell = harness.resolve(spec, "tiny.batch16", root=FIX, dirs=[FIX])
    out = control.readings(cell, 2 ** 40 + 12)
    assert not out["correct"]
    assert out["wrong_answers"] > 0
