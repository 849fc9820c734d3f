"""The trace reduction, on fixed events and on a trace recorded here."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import trace

W = trace.WINDOW_SPAN


def test_summarize_fixed_events():
    spans = [(0, 1000, W),
             (100, 500, "chipbench.eval"), (100, 200, "chipbench.dispatch"),
             (200, 500, "chipbench.wait"),
             (600, 950, "chipbench.eval"), (600, 700, "chipbench.dispatch"),
             (700, 950, "chipbench.wait")]
    devices = [[(-50, 50, "fusion"), (250, 400, "gemm"), (350, 450, "gemm"),
                (720, 900, "syrk"), (990, 1200, "gemm")]]
    s = trace.summarize(spans, devices)
    assert s.window_s == pytest.approx(1000e-9)
    # busy: [0,50] + [250,450] + [720,900] + [990,1000] = 50+200+180+10
    assert s.busy_s == pytest.approx(440e-9)
    assert s.idle_share == pytest.approx(0.56)
    assert s.op_s == pytest.approx({"fusion": 50e-9, "gemm": 260e-9,
                                    "syrk": 180e-9})
    # gaps [50,250] dispatch (mid 150); [450,720] window only (mid 585);
    # [900,990] wait (mid 945 is inside the second eval's wait)
    assert s.gap_s == pytest.approx({"dispatch": 200e-9, "window": 270e-9,
                                     "wait": 90e-9})
    bd = s.breakdown()
    assert bd["device_ops"][0] == ["gemm", pytest.approx(260e-9)]
    assert [k for k, _ in bd["idle_gaps"]] == ["window", "dispatch", "wait"]


def test_busy_is_averaged_over_devices():
    spans = [(0, 100, W)]
    s = trace.summarize(spans, [[(0, 100, "a")], [(0, 50, "a")]])
    assert s.devices == 2 and s.busy_s == pytest.approx(75e-9)


def test_needs_one_window_and_a_device():
    with pytest.raises(ValueError):
        trace.summarize([], [[(0, 1, "a")]])
    with pytest.raises(ValueError):
        trace.summarize([(0, 1, W)], [])


def test_spans_of_a_recorded_trace_are_found(tmp_path):
    """A trace recorded here holds the window and the inner spans; the CPU
    has no device plane, so the reduction refuses it."""
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with trace.traced(tmp_path) as span:
        for _ in range(3):
            with span("chipbench.eval"):
                f(x).block_until_ready()
    spans, devices = trace.load_events(trace.find_xplane(tmp_path))
    names = [n for _, _, n in spans]
    assert names.count(W) == 1 and names.count("chipbench.eval") == 3
    assert devices == []
    with pytest.raises(ValueError, match="no device"):
        trace.reduce(trace.find_xplane(tmp_path))
