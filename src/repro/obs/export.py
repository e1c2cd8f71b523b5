"""One CSV assembler for the paper-figure scripts in ``benchmarks/``.

Every script there used to hand-roll its CSV — an ``io.StringIO``, a
hand-printed header, and f-string rows whose column order silently
drifted from the header's.  :class:`SweepReport` is the one assembler
they now share: columns are declared once, every row is validated
against them, and comment lines (the ``# ...`` context the scripts
interleave) ride along in order.
"""
from __future__ import annotations

from typing import List, Sequence


class SweepReport:
    """Column-checked CSV assembly for the benchmark sweeps.

    >>> rep = SweepReport("sweep", "ratio", "hit_rate")
    >>> rep.add(sweep="cache", ratio=0.01, hit_rate="0.9372")
    >>> rep.comment("steady state after 150 warmup batches")
    >>> print(rep.csv())

    Values are written with ``str()`` — callers keep formatting floats
    exactly as before (the column contract is order + presence, not
    precision).
    """

    def __init__(self, *columns: str):
        if not columns:
            raise ValueError("SweepReport needs at least one column")
        if len(set(columns)) != len(columns):
            raise ValueError(f"duplicate columns in {columns}")
        self.columns: Sequence[str] = columns
        self._lines: List[str] = []

    def add(self, **values) -> None:
        """Append one row; every declared column must be present and no
        extras allowed (the drift this class exists to prevent)."""
        missing = [c for c in self.columns if c not in values]
        extra = [k for k in values if k not in self.columns]
        if missing or extra:
            raise ValueError(
                f"row does not match columns {list(self.columns)}: "
                f"missing {missing}, unexpected {extra}")
        self._lines.append(",".join(str(values[c]) for c in self.columns))

    def comment(self, text: str) -> None:
        """Interleave a ``# ...`` context line at the current position."""
        self._lines.append(f"# {text}")

    @property
    def header(self) -> str:
        return ",".join(self.columns)

    def __len__(self) -> int:
        return sum(not ln.startswith("#") for ln in self._lines)

    def csv(self) -> str:
        """Header + rows/comments, newline-terminated."""
        return "\n".join([self.header, *self._lines]) + "\n"
