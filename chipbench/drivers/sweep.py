"""Traffic kind ``sweep``: the anomaly sweep over fixed points, pass after pass.

Each pass is ``repro.core.sweep.sweep(spec, points, runner=…)`` as
Experiment 1's loop calls it, with no atlas, on one runner made once by
``make_backend(backend, dtype=…, reps=…, seed=seed)``. Set-up runs one
pass; the window runs whole passes until ``seconds`` have passed.

What the window produced is checked after it: every record's FLOP counts
against the closed forms, and its cheapest / fastest classes and anomaly
flag against the times recorded beside them. Every algorithm's answer at
every point is then computed once more by the program the window timed,
``jax.jit(runner.build(alg))`` on the runner, with the timed shapes and
dtype, and compared with the reference on the same inputs.

Traffic file keys: ``points``, ``reps``, ``threshold``.
"""

from __future__ import annotations

import sys
import time
import traceback

from chipbench import check
from chipbench.harness import Check, Record
from chipbench.trace import traced

#: Times within this relative distance tie for fastest (the paper's rule).
TIME_TIE = 1e-9


def classify(times, flops, threshold):
    """(cheapest, fastest, time_score, is_anomaly), paper §3.3."""
    f_min = min(flops.values())
    cheapest = tuple(sorted(a for a, f in flops.items() if f == f_min))
    t_min = min(times.values())
    fastest = tuple(sorted(a for a, t in times.items()
                           if t <= t_min * (1 + TIME_TIE)))
    t_cheapest = min(times[a] for a in cheapest)
    score = max(0.0, (t_cheapest - t_min) / t_cheapest) \
        if t_cheapest > 0 else 0.0
    anomaly = not (set(cheapest) & set(fastest)) and score > threshold
    return cheapest, fastest, score, anomaly


class Session:
    def __init__(self, cell, expr, seed, devices, peak_flops, peak_bw,
                 control=False):
        import jax.numpy as jnp
        from repro.core.backends import make_backend
        from repro.core.expressions import get_spec

        cfg, traffic = cell.config, cell.traffic
        self.spec = get_spec(cfg["expression"])
        self.expr = expr
        self.seed = seed
        self.device = devices[0]
        self.dtype = jnp.dtype(cfg["dtype"])
        self.points = [tuple(int(x) for x in p) for p in traffic["points"]]
        self.reps = int(traffic["reps"])
        self.threshold = float(traffic["threshold"])
        self.runner = make_backend(cfg["backend"], dtype=cfg["dtype"],
                                   reps=self.reps, seed=seed)
        self.control = control
        self.answers = {}
        self.records = []

    def _pass(self):
        from repro.core.sweep import sweep
        return sweep(self.spec, self.points, runner=self.runner,
                     reps=self.reps, threshold=self.threshold)

    def setup(self):
        self._pass()

    def window(self, seconds, trace_dir):
        failed = 0
        records = []
        with traced(trace_dir) as span:
            t_start = time.perf_counter()
            deadline = t_start + seconds
            while True:
                with span("chipbench.pass"):
                    try:
                        records.extend(self._pass().records)
                    except Exception:
                        failed += len(self.points)
                        traceback.print_exc(file=sys.stderr)
                if time.perf_counter() >= deadline:
                    break
            window_s = time.perf_counter() - t_start
        self.records = records
        return Record(kind="sweep", window_s=window_s,
                      completed=len(records), failed=failed)

    def _inputs(self, point):
        """The point's operands, standard normal from the seed in the dtype."""
        shapes = self.expr.operand_shapes(point)
        arrays = check.device_normals(list(shapes.values()), self.dtype,
                                      [self.seed, *point], self.device)
        return dict(zip(shapes, arrays))

    def release(self):
        """Every algorithm's answer at every point, by the program the window
        timed (the runner jits ``runner.build(alg)``); then drop the runner."""
        import jax
        from repro.core.algorithms import enumerate_algorithms

        for p in self.points:
            ops = self._inputs(p)
            args = self.expr.call_args(ops)
            for alg in enumerate_algorithms(self.spec.chain(p)):
                if self.control:
                    out = check.reference(self.expr, ops, control=True)
                else:
                    out = jax.jit(self.runner.build(alg))(*args)
                self.answers[(p, alg.name)] = jax.block_until_ready(out)
        self.runner = None

    def check(self, limits):
        wrong_records = 0
        for inst in self.records:
            flops = self.expr.algorithm_flops(inst.point)
            cheapest, fastest, score, anomaly = classify(
                inst.times, flops, self.threshold)
            wrong_records += (
                inst.flops != flops or tuple(inst.cls.cheapest) != cheapest
                or tuple(inst.cls.fastest) != fastest
                or bool(inst.cls.is_anomaly) != anomaly
                or abs(inst.cls.time_score - score) > 1e-12)
        gaps = []
        limit = float(limits["max_rel_gap"])
        wrong_points = 0
        for p in self.points:
            ref = check.reference(self.expr, self._inputs(p))
            point_gaps = [check.rel_gap(self.answers.get((p, name)), ref)
                          for name in self.expr.algorithm_flops(p)]
            gaps.extend(point_gaps)
            wrong_points += max(point_gaps) > limit
        worst = max(gaps) if gaps else float("inf")
        return [Check("max_rel_gap", worst, limit),
                Check("records_wrong", float(wrong_records),
                      float(limits["records_wrong"]))], (
            wrong_records + wrong_points)
