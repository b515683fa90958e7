"""One line a comparison: name, pass or fail, what was seen, its limit."""

from __future__ import annotations

from dataclasses import dataclass
from typing import List


@dataclass(frozen=True)
class Check:
    name: str
    ok: bool
    observed: float
    limit: float
    note: str = ""

    def line(self) -> str:
        return (f"check {self.name}: {'PASS' if self.ok else 'FAIL'} "
                f"observed={self.observed!r} limit={self.limit!r}"
                + (f" ({self.note})" if self.note else ""))


def at_most(name: str, observed: float, limit: float, note: str = "") -> Check:
    """Passes when observed <= limit; a NaN never passes."""
    observed = float(observed)
    return Check(name, bool(observed <= limit), observed, float(limit), note)


def exactly(name: str, observed: float, wanted: float, note: str = "") -> Check:
    observed = float(observed)
    return Check(name, bool(observed == wanted), observed, float(wanted), note)


def all_ok(checks: List[Check]) -> bool:
    return bool(checks) and all(c.ok for c in checks)
