"""chip_smoke.py on the CPU: its phases at ``smoke()`` scale with the
kernels in the Pallas interpreter, and its refusal to run without a TPU.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from repro.configs import dlrm as dlrm_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
SMALL = dict(batches=4, batch=16, slots=64)


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _phases(out: str):
    return [json.loads(line) for line in out.splitlines()
            if line.startswith('{"phase"')]


def test_one_chip_phases_interpret(capsys):
    _load().run_one_chip(dlrm_cfg.smoke(), "interpret", **SMALL)
    phases = _phases(capsys.readouterr().out)
    assert [p["phase"] for p in phases] == ["local", "tiered", "pipelined"]
    for p in phases:
        assert p["batches"] == 4 and p["fallbacks"] == 0, p
    assert phases[0]["max_dscore_vs_reference"] <= phases[0]["bound"]
    assert phases[1]["max_dscore_vs_local"] == 0.0
    assert phases[2]["max_dscore_vs_local"] == 0.0


def test_four_chip_phases_interpret():
    """The ``--four-chips`` paths on 4 forced host devices."""
    code = (f"import sys; sys.path.insert(0, {ROOT!r}); import chip_smoke; "
            f"from repro.configs.dlrm import smoke; "
            f"chip_smoke.run_four_chips(smoke(), 'interpret', **{SMALL!r})")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.path.join(ROOT, "src")}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    phases = _phases(r.stdout)
    assert [p["phase"] for p in phases] == [
        "rw_allgather_bulk", "rw_allgather_onesided", "rw_a2a_bulk",
        "rw_a2a_onesided", "remote_bulk", "remote_onesided"]
    for p in phases:
        assert p["max_dscore_vs_reference"] <= p["bound"], p
    assert all(p["misses_remote"] > 0 for p in phases[4:])


def test_main_refuses_without_tpu(capsys):
    assert _load().main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "no TPU" in err


@pytest.mark.parametrize("alone", [False, True])
def test_script_exits_nonzero_off_tpu(tmp_path, alone):
    """As run from a checkout, and copied into a directory that holds
    nothing else of the repo: a non-zero exit and no result line."""
    script = SCRIPT
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, script], env=env,
                       cwd=os.path.dirname(script), capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
