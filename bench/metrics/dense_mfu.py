"""Per-layer metric dense_mfu: the dense part of the model step (MLPs and
cross net) as a share of the chips' bf16 peak, over the device time of
every op in the window but the TBE kernel.  Reads the model reference's
``dense_flops_per_sample``; None for a model without one."""
from __future__ import annotations

import importlib


def read(run):
    """100 x dense operations per sample x samples scored in the window /
    (device seconds of the window's ops that do not match the adapter's
    ``TBE_KERNEL``, summed over chips, x one chip's bf16 peak)."""
    if run.trace is None or run.peaks is None or not run.window.scored:
        return None
    model = run.cell.config["model"]
    dense_flops = getattr(
        importlib.import_module(f"bench.models.{model}_reference"),
        "dense_flops_per_sample", None)
    if dense_flops is None:
        return None
    kernel = importlib.import_module(f"bench.models.{model}").TBE_KERNEL
    device_s = sum(sum(ops.values()) for ops in run.trace.op_s)
    dense_s = device_s - sum(run.trace.matching_s(kernel))
    if dense_s <= 0:
        return None
    return (100.0 * dense_flops(run.cell.config) * run.window.scored
            / (dense_s * run.peaks["flops"]))
