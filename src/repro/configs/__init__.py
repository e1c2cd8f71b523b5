"""Config registry: ``get_config("<arch-id>")`` / ``get_smoke_config``.

One module per assigned architecture (exact published config) plus the
paper's own DLRM. ``ARCH_IDS`` is the assignment list (10 archs).  The
LM dataclasses live in ``repro.configs.base``, imported only by the LM
modules, so ``repro.configs.dlrm`` loads no LM code.
"""
from __future__ import annotations

import importlib

ARCH_IDS = [
    "moonshot-v1-16b-a3b",
    "deepseek-v3-671b",
    "hymba-1.5b",
    "starcoder2-15b",
    "yi-34b",
    "granite-8b",
    "nemotron-4-340b",
    "whisper-base",
    "internvl2-2b",
    "rwkv6-1.6b",
]

_MODULES = {a: "repro.configs." + a.replace("-", "_").replace(".", "_")
            for a in ARCH_IDS}


def _module(name: str):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[name])


def get_config(name: str):
    return _module(name).CONFIG


def get_smoke_config(name: str):
    return _module(name).smoke()


def shape_cells(arch: str):
    """The (shape, reason-or-None) cells assigned to ``arch``.

    Returns list of (ShapeConfig, skip_reason|None). long_500k runs only
    for ssm/hybrid families (sub-quadratic decode state).
    """
    from repro.configs.base import SHAPES

    cfg = get_config(name=arch) if isinstance(arch, str) else arch
    cells = []
    for s in SHAPES.values():
        skip = None
        if s.name == "long_500k" and not cfg.supports_long_context:
            skip = "full-attention arch: O(S^2)/O(S) decode state at 500k " \
                   "is out of assignment scope"
        cells.append((s, skip))
    return cells
