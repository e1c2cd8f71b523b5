"""Import layering: the DLRM serving path loads no LM code, ``core``
depends on no config registry, and the paper-figure scripts time
nothing (``bench/run.py`` is the one place that measures speed)."""
import ast
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")

LM_MODULES = (
    "repro.models.lm", "repro.models.decode", "repro.models.layers",
    "repro.models.mla", "repro.models.moe", "repro.models.ssm",
    "repro.core.routing", "repro.configs.base",
)


def _imported_modules(path):
    """Every module name an ``import`` statement in ``path`` names."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module)
            names.update(f"{node.module}.{a.name}" for a in node.names)
    return names


def test_serving_engine_loads_no_lm_module():
    code = ("import json, sys\n"
            "import repro.serving.engine\n"
            "print(json.dumps(sorted(m for m in sys.modules\n"
            "                        if m.startswith('repro'))))\n")
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    loaded = set(json.loads(proc.stdout.strip().splitlines()[-1]))
    assert "repro.serving.engine" in loaded
    assert not loaded & set(LM_MODULES), sorted(loaded & set(LM_MODULES))


def test_core_imports_no_configs():
    core = os.path.join(SRC, "repro", "core")
    offenders = {
        name: sorted(m for m in _imported_modules(os.path.join(core, name))
                     if m == "repro.configs" or m.startswith("repro.configs."))
        for name in sorted(os.listdir(core)) if name.endswith(".py")}
    assert "parallel.py" in offenders
    assert not {k: v for k, v in offenders.items() if v}


def test_benchmark_scripts_read_no_clock():
    bench = os.path.join(ROOT, "benchmarks")
    clocks = {"time", "timeit"}
    offenders = {
        name: sorted(_imported_modules(os.path.join(bench, name)) & clocks)
        for name in sorted(os.listdir(bench)) if name.endswith(".py")}
    assert "run.py" in offenders
    assert not {k: v for k, v in offenders.items() if v}
