"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script itself refuses to run without a TPU; its phases are driven here
directly, with the same checks, so a wrong path, argument or mesh shows up
before any chip time is spent.  The four-chip phase runs on four virtual
CPU devices in a subprocess (the main test process keeps one device).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke as smoke  # noqa: E402

TINY = smoke.Shape(n=3000, dim=16, universe=64, queries=64, batch=16,
                   inserts=300, deletes=40, delta_cap=256)
TINY_INDEX = dict(num_hashes=8, width=24, num_probes=30, candidate_cap=32)


def test_one_chip_phase_checks_pass_on_cpu():
    lines = []
    checks = smoke.run_one_chip(
        TINY, smoke.index_config(TINY, **TINY_INDEX),
        smoke.serve_config(TINY, cand_bucket_min=64), seed=0,
        log=lambda stage, fields: lines.append(stage))
    assert checks and all(checks.values()), checks
    assert {"build", "warmup", "recall", "mutate"} <= set(lines), lines


def test_main_refuses_without_tpu(capsys):
    assert smoke.main([]) == 2
    assert smoke.main(["--chips", "4"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "TPU" in out.err


@pytest.mark.parametrize("var", smoke.REFUSED_ENV)
def test_main_refuses_executor_overrides(monkeypatch, capsys, var):
    monkeypatch.setenv(var, "1")
    assert smoke.main([]) == 2
    assert capsys.readouterr().out == ""


FOUR_DEVICES = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {root!r})
    import jax
    import chip_smoke as smoke
    shape = smoke.Shape(**{shape!r})
    cfg = smoke.index_config(shape, **{index!r})
    checks = smoke.run_four_chips(shape, cfg, 0, jax.devices()[:4],
                                  lambda stage, fields: None)
    print("RESULT" + json.dumps(checks))
""")


def test_four_chip_phase_checks_pass_on_virtual_devices():
    shape = dict(n=4096, dim=16, universe=64, queries=32, batch=16)
    script = FOUR_DEVICES.format(root=ROOT, shape=shape, index=TINY_INDEX)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines() if ln.startswith("RESULT")]
    checks = json.loads(line[0][len("RESULT"):])
    assert checks and all(checks.values()), checks
