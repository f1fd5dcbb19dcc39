"""telemetry.snapshot — the JSON read path.

The same registry the /metrics endpoint scrapes, shaped for rspc
consumers (the explorer's diagnostics pane) and for in-process readers
(health verdicts, history samplers).
"""

from __future__ import annotations

from typing import Any

from .registry import REGISTRY
from .spans import recent_spans


def snapshot() -> dict[str, Any]:
    return {
        "metrics": REGISTRY.snapshot(),
        "spans": recent_spans(),
    }


def histogram_recent(name: str, **labels: Any) -> list[float]:
    """Raw recent observations of a histogram series ([] when the
    metric is unknown)."""
    fam = REGISTRY.get(name)
    if fam is None or not hasattr(fam, "recent"):
        return []
    return fam.recent(**labels)


def gauge_value(name: str, default: float = 0.0, **labels: Any) -> float:
    fam = REGISTRY.get(name)
    if fam is None or not hasattr(fam, "value"):
        return default
    return fam.value(**labels)


def counter_value(name: str, default: float = 0.0, **labels: Any) -> float:
    return gauge_value(name, default, **labels)
