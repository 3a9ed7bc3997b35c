"""The benchmark's harness on the CPU: names resolve, generators are
deterministic, the reference agrees with numpy, byte counts match a hand
count, and the entry point refuses to run without a TPU."""
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness  # noqa: E402

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_workload_resolves_to_its_files(workload):
    cell = harness.resolve(SPEC, workload)
    conf = cell["config"]
    assert conf["name"] == cell["cell"]["config"]
    assert {"data", "index", "serve", "guarantees"} <= set(conf)
    assert callable(cell["drive"])
    assert "seed" not in conf["data"]          # the data come from --seed
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
    assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for m in cell["per_layer"]:
        assert callable(cell["readers"][m["name"]])
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}


def test_spec_names_units_and_paths_keep_to_the_contract():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in SPEC[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all("\n" not in layer for layer in layers)


def test_a_cell_that_exists_only_as_a_fixture_resolves():
    """Adding a cell, configuration, mix, loop or metric is adding files:
    these cells, their configuration, mixes, one loop and one metric exist
    only under the fixtures; the rest is found in bench/."""
    spec = harness.load_spec(os.path.join(FIX, "BENCHMARK.json"))
    cell = harness.resolve(spec, "tiny.batch16", root=FIX, dirs=[FIX])
    assert cell["config"]["data"]["n"] == 4096
    assert cell["traffic"]["batch"] == 16
    assert cell["traffic"]["recall_queries"] == 64
    assert cell["drive"].__code__.co_filename == os.path.join(
        harness.BENCH, "loops", "closed.py")
    assert set(cell["readers"]) == {"served_batches", "build_s"}
    assert cell["readers"]["served_batches"]({"batches": 3}, None) == 3
    assert cell["readers"]["build_s"]({"build_s": 1.5}, None) == 1.5
    fixed = harness.resolve(spec, "tiny.fixed2", root=FIX, dirs=[FIX])
    assert set(fixed["readers"]) == {"build_s"}
    pool = np.arange(64 * 2, dtype=np.int32).reshape(64, 2)
    out = fixed["drive"](_Echo(), pool, fixed["traffic"], 0.0, _no_span,
                         np.random.default_rng(0), lambda: None)
    assert out["attempted"] == 32 and out["batches"] == 2
    assert np.array_equal(out["ids"][:, 0], pool[:32, 0])
    with pytest.raises(KeyError):
        harness.resolve(spec, "no.such.cell", root=FIX, dirs=[FIX])


def test_generators_are_deterministic_in_the_seed():
    from bench import data as bd
    law = dict(n=512, dim=8, universe=64, num_clusters=4,
               cluster_spread=0.03)

    def make(seed):
        keys = bd.seed_keys(seed)
        x = bd.make_data(keys["data"], **law)
        q = bd.make_queries(keys["queries"], x, count=32, universe=64,
                            perturb_frac=0.02)
        return np.asarray(x), np.asarray(q)

    a, b = make(3), make(3)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    big = 2 ** 40 + 3
    c = make(big)
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(make(2 ** 40)[0], make(0)[0])
    x = a[0]
    assert x.dtype == np.int32 and x.shape == (512, 8)
    assert x.min() >= 0 and x.max() <= 64 and not (x % 2).any()


def test_reference_agrees_with_numpy_l1():
    from bench import reference as ref
    rng = np.random.default_rng(5)
    x = 2 * rng.integers(0, 256, size=(700, 24), dtype=np.int32)
    q = 2 * rng.integers(0, 256, size=(40, 24), dtype=np.int32)
    full = np.abs(x[None].astype(np.int64) - q[:, None]).sum(-1)
    assert np.array_equal(ref.knn_dist(x, q, 10), np.sort(full, 1)[:, :10])
    ids = rng.integers(0, 700, size=(40, 10))
    assert np.array_equal(ref.l1_exact(x, q, ids),
                          np.take_along_axis(full, ids, 1))


def test_compare_counts_every_kind_of_wrong_answer():
    from bench import reference as ref
    rng = np.random.default_rng(6)
    x = 2 * rng.integers(0, 256, size=(300, 16), dtype=np.int32)
    q = x[:20] + 2
    full = np.abs(x[None].astype(np.int64) - q[:, None]).sum(-1)
    ids = np.argsort(full, 1, kind="stable")[:, :10]
    d = np.take_along_axis(full, ids, 1)
    good = ref.compare(x, q, d, ids, 20, 10)
    assert good == {"wrong_answers": 0, "failed_queries": 0,
                    "recall_at_10": 1.0}
    bad_d, bad_i = d.copy(), ids.copy()
    bad_d[0, 0] += 2                       # a distance altered
    bad_i[1, 5] = bad_i[1, 4]              # an id repeated
    bad_i[2, 9] = -1                       # an id that is no row
    bad_d[3, [2, 3]] = bad_d[3, [3, 2]]    # two answers out of order
    bad_i[3, [2, 3]] = bad_i[3, [3, 2]]
    out = ref.compare(x, q, bad_d, bad_i, 20, 10)
    assert out["failed_queries"] == 4
    assert out["wrong_answers"] >= 4
    assert out["recall_at_10"] < 1.0


def test_phase_b_bytes_match_a_hand_count():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "roofline", os.path.join(harness.BENCH, "metrics",
                                 "phase_b_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    reader = mod.read
    # 1,000 candidates: 128 int32 coordinates + one int32 id = 516 bytes
    # each; 128 queries of 128 int32 = 65,536 bytes.
    assert mod.phase_b_bytes(1000, 128, 128, 4) == 516_000 + 65_536
    run = {"candidates": 1000, "batches": 2, "attempted": 128, "dim": 128,
           "itemsize": 4, "peaks": {"hbm_bytes_per_s": 581_536.0}}
    trace = {"programs": {"jit__finish_segment": {"seconds": 4.0,
                                                  "count": 2}}}
    assert reader(run, trace) == pytest.approx(25.0)
    assert reader(run, None) is None
    assert reader({k: v for k, v in run.items() if k != "candidates"},
                  trace) is None


def test_peak_table_is_keyed_by_device_kind():
    v5e = harness.read_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.read_peaks("cpu")


def _run(args, cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ["--workload", "sift1m.batch64", "--seed", "2147483653",
        "--seconds", "1", "--trace", "0"]


def test_run_exits_nonzero_with_no_result_without_a_tpu():
    res = _run(ARGS, ROOT)
    assert res.returncode != 0
    assert res.stdout == ""
    assert "TPU" in res.stderr


def test_run_exits_nonzero_where_only_the_benchmark_is(tmp_path):
    """A directory with BENCHMARK.json and the benchmark's paths alone has
    no program to run."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    res = _run(ARGS, tmp_path)
    assert res.returncode != 0
    assert res.stdout == ""
    assert not (tmp_path / ".jax_cache").exists() or not any(
        (tmp_path / ".jax_cache").iterdir())


class _Echo:
    """Answers each query with its first coordinate as every id, and
    counts the batches."""

    def __init__(self):
        self.batches = 0

    def query_batch(self, q):
        self.batches += 1
        return np.zeros((len(q), 3), np.int32), q[:, :1].repeat(3, 1)


def _no_span(name):
    import contextlib
    return contextlib.nullcontext()


def test_closed_loop_cycles_the_pool_in_an_order_drawn_from_the_seed():
    from bench.loops import closed
    pool = np.arange(40, dtype=np.int32).reshape(20, 2)
    mix = {"loop": "closed", "batch": 8, "recall_queries": 48}

    def drive(seed, seconds=0.0):
        engine, marks = _Echo(), []
        out = closed.drive(engine, pool, mix, seconds, _no_span,
                           np.random.default_rng(seed),
                           lambda: marks.append(engine.batches))
        return out, engine, marks

    (a, engine, marks), (b, _, _), (c, _, _) = drive(7), drive(7), \
        drive(2 ** 40 + 7)
    assert a["batches"] == 1 and a["attempted"] == 8
    assert marks == [1]                       # closed once, at the window's end
    assert engine.batches == 6                # the recall's 48 queries answered
    assert a["queries"].shape == (48, 2) and a["recall_queries"] == 48
    rows = a["queries"][:, 0] // 2
    for p in (rows[:20], rows[20:40]):        # each pass sends the whole pool
        assert sorted(p) == list(range(20))
    assert not np.array_equal(rows[:20], rows[20:40])
    assert np.array_equal(a["queries"], b["queries"])
    assert not np.array_equal(a["queries"], c["queries"])
    assert np.array_equal(a["ids"][:, 0], a["queries"][:, 0])
    assert a["metrics"]["queries_per_s"] > 0
    long, engine, marks = drive(7, seconds=0.05)
    assert long["attempted"] == 8 * long["batches"] >= 8
    assert marks == [long["batches"]]


def test_rung_readers_match_a_hand_count():
    import importlib.util

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(harness.BENCH, "metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    # three batches of 64 queries on 131,072 slots each, one on 262,144
    run = {"rungs": {131072: 3, 262144: 1}, "batches": 4, "attempted": 256}
    assert reader("rung_mean")(run, None) == 163840.0
    run["candidates"] = 64 * 163840 * 4 // 2
    assert reader("candidate_fill")(run, None) == pytest.approx(50.0)
    assert reader("candidate_fill")({"rungs": run["rungs"]}, None) is None
    assert reader("rung_mean")({"rungs": {}}, None) is None
