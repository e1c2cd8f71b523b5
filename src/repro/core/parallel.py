"""ParallelContext — carries mesh/axis knowledge through the models.

Models take ``ctx: ParallelContext | None``. ``None`` means single-device
(smoke tests, kernel oracles). With a context, the row-wise-sharded
embedding bag runs its explicit shard_map collectives over the tp axis,
and the dense layers are left to GSPMD, steered by the spec helpers
below.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    mesh: Mesh
    dp_axes: Tuple[str, ...]            # ("pod", "data") or ("data",)
    tp_axis: str                        # "model"

    @property
    def dp_size(self) -> int:
        s = 1
        for a in self.dp_axes:
            s *= self.mesh.shape[a]
        return s

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]

    # ---- spec helpers ------------------------------------------------------
    def dp_for(self, dim: int):
        """The dp axes usable to shard a dim of this size (divisibility)."""
        usable = []
        prod = 1
        for a in self.dp_axes:
            if dim % (prod * self.mesh.shape[a]) == 0:
                usable.append(a)
                prod *= self.mesh.shape[a]
        return tuple(usable) or None

    def tp_for(self, dim: int):
        return self.tp_axis if dim % self.tp_size == 0 else None

    def batch_spec(self, batch: int, extra_dims: int = 1) -> P:
        """P over the batch dim (dp axes when divisible) + replicated rest."""
        return P(self.dp_for(batch), *([None] * extra_dims))

    def constrain(self, x, spec: P):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)


def make_context(mesh: Mesh) -> ParallelContext:
    """Infer axes from the mesh: last axis = tp, rest = dp."""
    names = mesh.axis_names
    return ParallelContext(mesh=mesh, dp_axes=tuple(names[:-1]),
                           tp_axis=names[-1])
