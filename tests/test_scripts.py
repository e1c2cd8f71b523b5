"""The kept paper-figure scripts and the DLRM examples run to the end of
their own checks: each runs as a fresh interpreter on the CPU, and the
line it prints only after its asserts have passed must appear."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (argv after the interpreter, forced CPU device count, closing line)
SCRIPTS = {
    "cache_sweep": (["benchmarks/cache_sweep.py", "--smoke"], 1,
                    "# zipf a=1.2 hit-rate vs cache ratio:"),
    "tiering_sweep": (["benchmarks/tiering_sweep.py", "--smoke"], 1,
                      "# cached placements:"),
    "plan_roundtrip_sweep": (["benchmarks/plan_roundtrip_sweep.py",
                              "--smoke"], 1,
                             "# OK: plan prices check out"),
    "paper_figures": (["-m", "benchmarks.run"], 1, "# best: zipf="),
    "quickstart": (["examples/quickstart.py"], 1,
                   "OK: the distributed pipeline reproduces"),
    "dlrm_inference": (["examples/dlrm_inference.py"], 8,
                       "OK: every sharding strategy reproduces the oracle."),
    "dlrm_sharding_plan": (["examples/dlrm_sharding_plan.py"], 1,
                           "OK: giants are always row-split"),
    "serve_batched": (["examples/serve_batched.py"], 1,
                      "scores equal (depth 2 vs depth 1)"),
}


@pytest.mark.parametrize("name", sorted(SCRIPTS))
def test_script_runs_its_checks(name, tmp_path):
    argv, devices, closing = SCRIPTS[name]
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    proc = subprocess.run([sys.executable, *argv], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert closing in proc.stdout, proc.stdout[-4000:]
