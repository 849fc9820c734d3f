"""Traffic kind ``plan``: one caller in a closed loop over the planner.

The caller builds ``Planner(backend=…)`` as a user does, with its default
discriminant, and evaluates ``planner(chain, *arrays)`` for each slot of
the traffic's instance list, waiting on each result. Each cycle visits
the slots in a seeded shuffle; the window runs whole cycles until
``seconds`` have passed. Operands are made on the device from the seed,
one set per slot; shapes never depend on the seed.

Traffic file key: ``instances``, a list of ``{"dims": [...], "count": n}``.
``KEEP_PER_SLOT`` answers of each slot are kept, by a seeded reservoir,
for the comparison after the window.
"""

from __future__ import annotations

import sys
import time
import traceback

import numpy as np

from chipbench import check
from chipbench.harness import Check, Record
from chipbench.trace import traced

KEEP_PER_SLOT = 2


class Session:
    def __init__(self, cell, expr, seed, devices, peak_flops, peak_bw,
                 control=False):
        import jax.numpy as jnp
        from repro.core.expressions import get_spec
        from repro.core.planner import Planner

        cfg, traffic = cell.config, cell.traffic
        spec = get_spec(cfg["expression"])
        self.expr = expr
        self.control = control
        self.slots = [tuple(int(x) for x in inst["dims"])
                      for inst in traffic["instances"]
                      for _ in range(int(inst.get("count", 1)))]
        self.chains = [spec.chain(d) for d in self.slots]
        self.planner = Planner(backend=cfg["backend"])
        self.order_rng = np.random.default_rng([seed, 0])
        self.keep_rng = np.random.default_rng([seed, 1])
        dtype = jnp.dtype(cfg["dtype"])
        shapes = [expr.operand_shapes(d) for d in self.slots]
        flat = check.device_normals(
            [s for per in shapes for s in per.values()], dtype, seed,
            devices[0])
        it = iter(flat)
        self.operands = [{b: next(it) for b in per} for per in shapes]
        self.args = [expr.call_args(ops) for ops in self.operands]
        self.flops = [min(expr.algorithm_flops(d).values())
                      for d in self.slots]
        self.least_s = [
            max(f / peak_flops,
                expr.compulsory_elements(d) * dtype.itemsize / peak_bw)
            for f, d in zip(self.flops, self.slots)]
        self.peak_flops = peak_flops
        self.kept = {}

    def _evaluate(self, i):
        if self.control:
            return check.reference(self.expr, self.operands[i], control=True)
        return self.planner(self.chains[i], *self.args[i])

    def setup(self):
        """Plan and compile every distinct instance once."""
        import jax
        seen = set()
        for i, d in enumerate(self.slots):
            if d not in seen:
                seen.add(d)
                jax.block_until_ready(self._evaluate(i))

    def _offer(self, i, n_seen, out):
        """Seeded reservoir of ``KEEP_PER_SLOT`` answers per slot."""
        kept = self.kept.setdefault(i, [])
        if len(kept) < KEEP_PER_SLOT:
            kept.append(out)
        else:
            j = int(self.keep_rng.integers(0, n_seen))
            if j < KEEP_PER_SLOT:
                kept[j] = out

    def window(self, seconds, trace_dir):
        import jax

        lat, host = [], []
        failed = 0
        seen = [0] * len(self.slots)
        flops = least = 0.0
        with traced(trace_dir) as span:
            t_start = time.perf_counter()
            deadline = t_start + seconds
            while True:
                for i in self.order_rng.permutation(len(self.slots)):
                    with span("chipbench.eval"):
                        t0 = time.perf_counter()
                        try:
                            with span("chipbench.dispatch"):
                                out = self._evaluate(i)
                            t1 = time.perf_counter()
                            with span("chipbench.wait"):
                                out = jax.block_until_ready(out)
                        except Exception:
                            failed += 1
                            traceback.print_exc(file=sys.stderr)
                            continue
                        t2 = time.perf_counter()
                    lat.append(t2 - t0)
                    host.append(t1 - t0)
                    flops += self.flops[i]
                    least += self.least_s[i]
                    seen[i] += 1
                    self._offer(i, seen[i], out)
                if time.perf_counter() >= deadline:
                    break
            window_s = time.perf_counter() - t_start
        return Record(kind="plan", window_s=window_s, completed=len(lat),
                      failed=failed, latencies_s=lat, host_s=host,
                      required_flops=flops, least_time_s=least,
                      peak_flops=self.peak_flops,
                      regret=None if self.control else self.regret)

    def regret(self, reps=5):
        """Σ time of the planner's pick ÷ Σ time of the fastest algorithm,
        over the distinct instances, every algorithm run as the planner
        runs its pick (the backend-built callable), synced."""
        import jax
        from repro.core.algorithms import enumerate_algorithms

        def timed(fn, args):
            jax.block_until_ready(fn(*args))
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

        pick_s = best_s = 0.0
        seen = set()
        for i, d in enumerate(self.slots):
            if d in seen:
                continue
            seen.add(d)
            chain, args = self.chains[i], self.args[i]
            pick = self.planner.plan(chain).algorithm.name
            times = {a.name: timed(self.planner.runner.build(a), args)
                     for a in enumerate_algorithms(chain)}
            pick_s += times[pick]
            best_s += min(times.values())
        return pick_s / best_s

    def release(self):
        self.planner = None

    def check(self, limits):
        gaps = []
        for i, outs in sorted(self.kept.items()):
            ref = check.reference(self.expr, self.operands[i])
            gaps.extend(check.rel_gap(out, ref) for out in outs)
        limit = float(limits["max_rel_gap"])
        worst = max(gaps) if gaps else float("inf")
        wrong = sum(g > limit for g in gaps)
        return [Check("max_rel_gap", worst, limit)], wrong
