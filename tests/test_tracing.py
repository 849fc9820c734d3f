"""Spans and counters of ``repro.runtime.tracing``, on the CPU.

Spans record only inside a profiler session; the planner, the step walker,
the backends and the sweep engine open them at their boundaries.
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core.backends import make_backend
from repro.core.expressions import get_spec
from repro.core.planner import Planner
from repro.core.sweep import sweep
from repro.runtime import tracing

AATB_DIMS = (24, 40, 16)
ABCD_POINT = (16, 24, 8, 32, 12)


def _aatb_args(dims):
    m, k, n = dims
    a = jnp.arange(m * k, dtype=jnp.float32).reshape(m, k) / (m * k)
    b = jnp.ones((m, n), jnp.float32)
    return a, a, b


def _children_total(snap, names):
    return sum(snap[n][1] for n in names)


def test_span_without_a_session_is_the_shared_noop():
    tracing.reset()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    first = tracing.span("planner.call", request=True)
    assert first is tracing.span("step.gemm", kind="gemm")
    with first:
        with tracing.span("step.gemm"):
            pass
    Planner(backend="pallas")(get_spec("aatb").chain(AATB_DIMS),
                              *_aatb_args(AATB_DIMS))
    assert tracing.snapshot() == {}


@pytest.fixture(scope="module")
def planner_trace(tmp_path_factory):
    """Two evaluations by a fresh planner (a plan miss, then a hit) inside
    one profiler session, its kernels compiled beforehand."""
    chain = get_spec("aatb").chain(AATB_DIMS)
    args = _aatb_args(AATB_DIMS)
    jax.block_until_ready(Planner(backend="pallas")(chain, *args))
    tracing.reset()
    out_dir = tmp_path_factory.mktemp("trace")
    planner = Planner(backend="pallas")
    with jax.profiler.trace(str(out_dir)):
        for _ in range(2):
            jax.block_until_ready(planner(chain, *args))
    snap = tracing.snapshot()
    tracing.reset()
    return snap, out_dir


def test_planner_call_spans_nest_and_add_up(planner_trace):
    snap, _ = planner_trace
    assert snap["repro.planner.call"][0] == 2
    assert snap["repro.planner.plan"][0] == 2
    assert snap["repro.planner.enumerate"][0] == 1
    assert snap["repro.planner.select"][0] == 1
    steps = [n for n in snap if n.startswith("repro.step.")]
    assert steps and all(snap[n][0] % 2 == 0 for n in steps)
    for parent, children in (
            ("repro.planner.call", ["repro.planner.plan", *steps]),
            ("repro.planner.plan", ["repro.planner.enumerate",
                                    "repro.planner.select"])):
        count, total, self_s = snap[parent]
        assert 0 < self_s < total
        assert self_s + _children_total(snap, children) == pytest.approx(
            total, rel=1e-9, abs=1e-9)
    for name in steps:
        count, total, self_s = snap[name]
        assert self_s == pytest.approx(total, rel=1e-9, abs=1e-12)


def test_planner_spans_are_on_a_host_plane_with_one_id_per_call(
        planner_trace):
    snap, out_dir = planner_trace
    from jax.profiler import ProfileData

    (path,) = Path(out_dir).glob("plugins/profile/*/*.xplane.pb")
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.PREFIX):
                    assert not plane.name.startswith("/device:")
                    events.append((ev.start_ns, ev.end_ns, ev.name,
                                   dict(ev.stats)["id"]))
    names = {name for _, _, name, _ in events}
    # Spans aggregate as (count, total, self) tuples; counters are ints.
    assert names == {n for n, v in snap.items()
                     if n.startswith(tracing.PREFIX) and isinstance(v, tuple)}
    calls = sorted(e for e in events if e[2] == "repro.planner.call")
    assert len(calls) == 2 and calls[0][3] != calls[1][3]
    for start, end, _, rid in calls:
        inside = [e for e in events if start <= e[0] and e[1] <= end]
        assert {e[3] for e in inside} == {rid}
        assert {e[2] for e in inside} >= {"repro.planner.plan",
                                          *(n for n in names
                                            if n.startswith("repro.step."))}


def test_jax_traces_counts_a_fresh_jit_and_not_a_cached_one(tmp_path):
    x = jnp.ones(8)
    fresh = jax.jit(lambda v: v * 3 + 1)
    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("probe"):
            jax.block_until_ready(fresh(x))
        after_fresh = tracing.snapshot()
        with tracing.span("probe"):
            jax.block_until_ready(fresh(x))
        after_cached = tracing.snapshot()
    tracing.reset()
    assert after_fresh.get("jax.traces", 0) >= 1
    assert after_fresh.get("jax.compiles", 0) >= 1
    for counter in tracing.COMPILE_EVENTS.values():
        assert after_cached.get(counter, 0) == after_fresh.get(counter, 0)
    assert after_cached["repro.probe"][0] == 2


def test_one_sweep_point_records_every_synced_call(tmp_path):
    spec = get_spec("abcd")
    runner = make_backend("pallas", reps=2)
    sweep(spec, [ABCD_POINT], runner=runner, reps=2)  # compile first
    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        res = sweep(spec, [ABCD_POINT], runner=runner, reps=2)
    snap = tracing.snapshot()
    tracing.reset()
    orders = len(spec.algorithms(ABCD_POINT))
    assert len(res.records) == 1 and orders == 6
    assert snap["repro.backend.call"][0] == orders * (1 + 2)
    assert snap["repro.sweep.point"][0] == 1
    assert snap["repro.sweep.pass"][0] == 1
    assert snap["repro.sweep.prepare"][0] == 1
    assert snap["repro.sweep.wait"][0] == 1
    assert snap.get("jax.compiles", 0) == 0
    assert not any(n.startswith("repro.step.") for n in snap)
    assert snap["repro.backend.call"][1] < snap["repro.sweep.pass"][1]
