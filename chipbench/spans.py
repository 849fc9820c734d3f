"""The program's own span aggregates, for the metric readers that read them.

``repro.runtime.tracing`` records only while a profiler session records,
and a run records only its traced window, so its ``snapshot()`` covers the
window. A program without that module, or a run that recorded nothing,
gives ``None``, and the metric is left out of the line.
"""

from __future__ import annotations

from typing import Optional

#: The spans of the kernel calls the step walker makes.
COMPUTE_STEPS = ("step.gemm", "step.syrk", "step.symm", "step.symm_r",
                 "step.chain_gemm", "step.gemm_syrk")
#: The spans of its eager data movement.
MOVE_STEPS = ("step.transpose", "step.tri2full")
COMPILE_COUNTERS = ("jax.traces", "jax.compiles")


def recorded(rec, kind: str, unit: str) -> Optional[dict]:
    """The program's snapshot where the run is of traffic ``kind`` and
    recorded at least one ``repro.<unit>`` span, else ``None``."""
    if rec.kind != kind:
        return None
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    snap = tracing.snapshot()
    return snap if count(snap, unit) else None


def count(snap: dict, name: str) -> int:
    return snap.get("repro." + name, (0, 0.0, 0.0))[0]


def total_s(snap: dict, *names: str) -> float:
    return sum(snap.get("repro." + n, (0, 0.0, 0.0))[1] for n in names)


def compiles(snap: dict) -> int:
    return sum(snap.get(name, 0) for name in COMPILE_COUNTERS)
