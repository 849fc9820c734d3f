#!/usr/bin/env python3
"""Bring-up proof: the planner and one full-width model on a TPU chip.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # four chips of one host

One chip runs three phases through the entry points a user calls:

* ``planner`` — ``Planner(backend=...)`` plans ``aatb``, ``abcd`` and
  ``abab`` at dims of 1,024 and more (inner dims of 4,096 included), and
  every enumerated algorithm runs on the ``jax`` and ``pallas`` backends at
  bfloat16 and float32, as a user's program would compile it, against a
  float64 numpy oracle. The compiled Pallas programs must contain
  ``tpu_custom_call``: Mosaic ran, not the interpreter.
* ``sweep`` — the sweep CLI (``repro.core.sweep`` main) measures an
  ``aatb`` grid on the ``pallas`` backend and must report ``measured=8``.
* ``serve`` — ``phi3_mini`` at its published widths with seeded bfloat16
  weights serves 4 prompts of 64 tokens, 16 new tokens each, through
  ``serve.decode.generate``; the decode logits must be finite and agree
  with the teacher-forced ``api.forward_train``, and every generated
  token must be the decode argmax (up to a bf16 near-tie).

``--four-chips`` runs only what exists across chips, each against its
one-device counterpart: the device-sharded sweep, and ``glm4_9b`` decode on
a (1, 4) ``data`` x ``model`` mesh (full depth, and 2 layers compared with
the same 2 layers on one device).

Every phase prints one line; any failure exits non-zero. The last line of
a passing run is one JSON object naming the device. Without a TPU the
script exits non-zero before any phase and prints no verdict.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import math
import os
import sys
import tempfile
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: (expression, point): dims of 1,024 and more; abcd's C and abab's B are
#: 4,096 wide, the inner dimension at which a fused kernel outgrows VMEM.
PLANNER_CASES = (
    ("aatb", (2048, 1024, 4096)),
    ("abcd", (1024, 1024, 4096, 1024, 1024)),
    ("abab", (1024, 1024, 4096)),
)
BACKENDS = ("jax", "pallas")
DTYPES = ("bfloat16", "float32")

#: Unit roundoff of bfloat16 (8-bit significand) and float32.
U_BF16 = 2.0 ** -8
U_F32 = 2.0 ** -24

#: float32 tolerances of the zoo's correctness gate
#: (tests/test_expressions.py): |err| <= F32_RTOL*|ref| + F32_RTOL*max|ref|.
F32_RTOL = 5e-4


def bf16_bound(n_products: int, k_max: int) -> float:
    """Error bound, relative to max|ref|, of a bfloat16 algorithm.

    Every stored product is rounded once to bfloat16 (unit roundoff u);
    the MXU accumulates in float32, adding at most ``k_max * 2**-24`` over
    a contraction of length ``k_max``. A rounding of one stored product
    reaches the result through the remaining products as a sum of
    random-sign terms, so it stays the size of ``u`` times the result.
    With ``n_products`` stored products the error is at most
    ``n_products * (u + k_max * 2**-24)`` of max|ref|; the bound doubles
    that for the operand re-rounding between kernels.
    """
    return 2.0 * n_products * (U_BF16 + k_max * U_F32)


def decode_bound(n_layers: int) -> float:
    """Relative RMS bound between bfloat16 decode and forward logits.

    The two paths compute the same bfloat16 model in different orders
    (a cached attention over ``max_s`` positions against a dense one over
    the prefix), so each of the ``2 * n_layers`` residual branches adds a
    difference of about ``u`` of the stream; independent differences grow
    as their square root. The bound is twice ``sqrt(2 * n_layers) * u``.
    """
    return 2.0 * math.sqrt(2 * n_layers) * U_BF16


class PhaseError(AssertionError):
    """A phase ran but its result is wrong."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseError(msg)


# ----------------------------------------------------------------- planner --

def _count_mosaic_kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def phase_planner(cases=PLANNER_CASES, backends=BACKENDS, dtypes=DTYPES,
                  seed: int = 0, require_mosaic: bool = True) -> dict:
    """Plan each case and run every algorithm on every backend and dtype.

    Returns per-(backend, dtype) Mosaic kernel counts and the worst error
    ratio (error over its bound; at most 1 passes).
    """
    import jax
    import numpy as np

    from repro.core.backends import make_backend, num_inputs
    from repro.core.expressions import get_spec
    from repro.core.planner import Planner

    failures = []
    summary = {}
    for backend in backends:
        for dtype in dtypes:
            kernels = 0
            worst = 0.0
            n_algs = 0
            for expr, point in cases:
                spec = get_spec(expr)
                planner = Planner(backend=backend)
                plan = planner.plan(spec.chain(point))
                algos = spec.algorithms(point)
                synth = make_backend(backend, dtype=dtype, seed=seed)
                operands = {}
                for alg in algos:
                    for base, arr in synth.make_operands(alg).items():
                        operands.setdefault(base, arr)
                ref = spec.reference_value(
                    point, {b: np.asarray(a, np.float64)
                            for b, a in operands.items()})
                scale = float(np.abs(ref).max())
                some = next(iter(operands.values()))

                def compile_alg(alg):
                    args = [operands.get(i, some)
                            for i in range(num_inputs(alg))]
                    compiled = jax.jit(planner.runner.build(alg)).lower(
                        *args).compile()
                    return compiled, args

                def check(alg, compiled, args):
                    out = np.asarray(compiled(*args), np.float64)
                    if dtype == "float32":
                        allowed = F32_RTOL * np.abs(ref) + F32_RTOL * scale
                    else:
                        n_products = sum(1 for c in alg.calls if c.flops)
                        allowed = bf16_bound(n_products, max(point)) * scale
                    err = np.abs(out - ref)
                    return out, err, float((err / allowed).max())

                # Compiling dominates; it releases the GIL, so overlap it.
                with ThreadPoolExecutor(max_workers=8) as pool:
                    built = list(pool.map(compile_alg, algos))
                for alg, (compiled, args) in zip(algos, built):
                    out, err, ratio = check(alg, compiled, args)
                    n_kernels = _count_mosaic_kernels(compiled)
                    kernels += n_kernels
                    worst = max(worst, ratio)
                    n_algs += 1
                    if not np.isfinite(out).all() or ratio > 1.0:
                        failures.append(
                            f"{backend}/{dtype} {expr}{point} {alg.name}: "
                            f"max err {float(err.max()):.3g} is "
                            f"{ratio:.3g}x its bound (max|ref| {scale:.3g})")
                    if backend == "pallas" and require_mosaic \
                            and n_kernels == 0:
                        failures.append(
                            f"pallas/{dtype} {expr} {alg.name}: no "
                            f"tpu_custom_call in the compiled program")
                print(f"  planner {backend}/{dtype} {expr}{point}: "
                      f"{len(algos)} algorithms, plan={plan.algorithm.name}",
                      flush=True)
            summary[f"{backend}/{dtype}"] = {
                "algorithms": n_algs, "tpu_custom_call": kernels,
                "worst_err_over_bound": worst}
            print(f"  planner {backend}/{dtype}: {n_algs} algorithms, "
                  f"tpu_custom_call={kernels}, worst err/bound={worst:.3g}",
                  flush=True)
    for f in failures:
        print(f"  FAIL {f}", flush=True)
    _check(not failures, f"{len(failures)} planner result(s) wrong")
    return summary


# ------------------------------------------------------------------- sweep --

def phase_sweep(expr: str = "aatb", backend: str = "pallas",
                grid: str = "1024,2048", expect: int = 8) -> str:
    """Run the sweep CLI in this process; returns its summary line."""
    from repro.core import sweep as sweep_cli

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = sweep_cli.main(["--expr", expr, "--backend", backend,
                             "--grid", grid, "--quiet"])
    text = out.getvalue()
    line = next((ln for ln in text.splitlines() if ln.startswith("sweep ")),
                "")
    _check(rc == 0, f"sweep CLI exited {rc}:\n{text}")
    _check(f"measured={expect} " in line,
           f"sweep CLI did not measure {expect} instances: {line!r}")
    _check("device=" in line, f"sweep line names no device: {line!r}")
    print(f"  {line}", flush=True)
    return line


# ------------------------------------------------------------------- serve --

def _bf16_params(cfg, seed: int, out_shardings=None):
    import jax
    import jax.numpy as jnp

    from repro.models import api

    init = jax.jit(lambda key: api.init(key, cfg, jnp.bfloat16)[0],
                   out_shardings=out_shardings)
    return init(jax.random.PRNGKey(seed))


def _decode_logits(params, cfg, tokens, max_s: int, jit_kw=None):
    """Teacher-forced decode: logits after each of ``tokens``' positions."""
    import jax
    import jax.numpy as jnp

    from repro.models import api

    b, s = tokens.shape
    kw = jit_kw or {}
    caches = jax.jit(lambda p: api.init_caches(p, cfg, b, max_s),
                     **kw.get("init", {}))(params)
    step = jax.jit(lambda p, t, c: api.decode_step(p, cfg, t, c),
                   donate_argnums=(2,), **kw.get("step", {}))
    out = []
    for i in range(s):
        logits, caches = step(params, tokens[:, i:i + 1], caches)
        out.append(logits[:, 0])
    return jnp.stack(out, axis=1)


def _rel_rms(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def phase_serve(cfg=None, batch: int = 4, prompt_len: int = 64,
                new: int = 16, max_s: int = 2048, seed: int = 0) -> dict:
    """Serve ``cfg`` (default: full-width phi3_mini) with bf16 weights."""
    import jax
    import numpy as np

    from repro.configs import get
    from repro.models import api
    from repro.serve.decode import generate

    cfg = cfg or get("phi3_mini")
    params = _bf16_params(cfg, seed)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, prompt_len), 0, cfg.vocab)
    t0 = time.perf_counter()
    tokens = jax.block_until_ready(
        generate(params, cfg, prompt, max_new=new, max_s=max_s))
    gen_s = time.perf_counter() - t0
    _check(tokens.shape == (batch, prompt_len + new),
           f"generate returned {tokens.shape}")
    toks = np.asarray(tokens)
    _check(bool((toks >= 0).all() and (toks < cfg.vocab).all()),
           "generated token ids out of range")
    _check(bool((toks[:, :prompt_len] == np.asarray(prompt)).all()),
           "generate changed the prompt")

    dec = np.asarray(_decode_logits(params, cfg, tokens, max_s), np.float32)
    full, _ = jax.jit(lambda p, t: api.forward_train(p, cfg, {"tokens": t}))(
        params, tokens)
    full = np.asarray(full, np.float32)
    _check(bool(np.isfinite(dec).all()), "decode logits are not finite")
    _check(bool(np.isfinite(full).all()), "forward logits are not finite")
    rel = _rel_rms(dec, full)
    bound = decode_bound(cfg.n_layers)
    # Greedy generation picks the decode argmax at every new position. Its
    # own compile of the same bf16 model may differ from this one by the
    # drift bound, so a pick may trail the argmax by at most that much of
    # the logits' RMS (a near-tie); a wrong token trails by far more.
    rows = dec[:, prompt_len - 1:-1]
    picked = toks[:, prompt_len:]
    gap = rows.max(-1) - np.take_along_axis(
        rows, picked[..., None], axis=-1)[..., 0]
    tie = bound * np.sqrt(np.mean(np.square(rows, dtype=np.float64), -1))
    agree = float((gap == 0).mean())
    print(f"  serve {cfg.name}: {n_params} params (bf16), batch={batch} "
          f"prompt={prompt_len} new={new} max_s={max_s}, generate "
          f"{gen_s:.3f}s (compile included), decode-vs-forward rel RMS "
          f"{rel:.3g} (bound {bound:.3g}), greedy agreement {agree:.3f}, "
          f"worst pick gap / tie bound {float((gap / tie).max()):.3g}",
          flush=True)
    _check(rel <= bound, f"decode logits drift from forward_train: rel RMS "
                         f"{rel:.3g} > {bound:.3g}")
    _check(bool((gap <= tie).all()),
           f"generate picked tokens that are not the decode argmax: "
           f"{int((gap > tie).sum())} of {gap.size} beyond a near-tie")
    return {"params": n_params, "rel_rms": rel, "bound": bound,
            "greedy_agreement": agree}


# ---------------------------------------------------------- four chips -----

def phase_sharded_sweep(n_devices: int = 4, expr: str = "aatb",
                        backend: str = "pallas",
                        values=(1024, 2048), seed: int = 0) -> dict:
    """The device-sharded sweep on ``n_devices`` against one device.

    A recording subclass of the backend notes the device of every operand
    it synthesizes and of every result it waits for, so each device is
    seen to measure its own shard on operands it holds.
    """
    import jax
    import numpy as np

    from repro.core import sweep as sweep_mod
    from repro.core.backends import get_backend_class, register_backend
    from repro.core.expressions import GridSpec, get_spec

    _check(len(jax.devices()) >= n_devices,
           f"{n_devices} devices needed, JAX sees {len(jax.devices())}")
    name = f"{backend}-recorded"
    base = get_backend_class(backend)
    seen = {"operands": {}, "results": {}}

    class Recorded(base):
        def _asarray(self, a):
            out = super()._asarray(a)
            for d in out.devices():
                seen["operands"][d.id] = seen["operands"].get(d.id, 0) + 1
            _check(out.devices() == {self.device},
                   f"operand on {out.devices()}, runner pinned to "
                   f"{self.device}")
            return out

        def _sync(self, out):
            out = super()._sync(out)
            for d in out.devices():
                seen["results"][d.id] = seen["results"].get(d.id, 0) + 1
            return out

    with contextlib.suppress(ValueError):  # already registered
        register_backend(name, Recorded)
    spec = get_spec(expr)
    points = GridSpec.uniform(values, spec.ndims).points()
    runs = {}
    for shards in (1, n_devices):
        seen["operands"].clear()
        seen["results"].clear()
        res = sweep_mod.sweep(spec, points, backend="jax", exec_backend=name,
                              shards=shards, reps=3, seed=seed,
                              dtype="float32")
        runs[shards] = (res, dict(seen["operands"]), dict(seen["results"]))
    one, multi = runs[1][0], runs[n_devices][0]
    _check(one.n_measured == multi.n_measured == len(points),
           f"measured {one.n_measured} / {multi.n_measured} of "
           f"{len(points)}")
    ids = {d.id for d in jax.devices()[:n_devices]}
    ops4, res4 = runs[n_devices][1], runs[n_devices][2]
    _check(set(ops4) == ids and set(res4) == ids,
           f"devices with operands {sorted(ops4)}, with results "
           f"{sorted(res4)}; expected {sorted(ids)}")
    _check(set(runs[1][1]) == {jax.devices()[0].id},
           f"one-device run placed operands on {sorted(runs[1][1])}")
    ratios = []
    for a, b in zip(one.records, multi.records):
        _check(a.point == b.point and a.flops == b.flops,
               f"instance {a.point} differs between 1 and {n_devices} "
               f"devices")
        ratios += [b.times[k] / a.times[k] for k in a.times]
    median = float(np.median(ratios))
    print(f"  sharded sweep {expr} {backend}: {len(points)} points; "
          f"1 device {one.wall_s:.3f}s, {n_devices} devices "
          f"{multi.wall_s:.3f}s; operands per device {ops4}, results per "
          f"device {res4}; median time ratio {n_devices}-dev/1-dev "
          f"{median:.3f}", flush=True)
    return {"points": len(points), "wall_1": one.wall_s,
            "wall_n": multi.wall_s, "operands": ops4, "results": res4,
            "median_time_ratio": median}


def phase_sharded_model(cfg=None, small_layers: int = 2, batch: int = 4,
                        prompt_len: int = 16, max_s: int = 256,
                        model_axis: int = 4, seed: int = 0) -> dict:
    """``cfg`` (default: full-width glm4_9b) decoding on a (1, 4) mesh.

    Shardings come from ``launch/specs.abstract_serve_state``. The full
    depth only fits across the chips; its logits must be finite. The same
    model cut to ``small_layers`` runs on the mesh and on ``devices[0]``,
    and the two must give the same logits.
    """
    import jax
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.configs import ShapeSpec, get
    from repro.launch import specs as specs_lib

    cfg = cfg or get("glm4_9b")
    mesh = jax.make_mesh((1, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    shape = ShapeSpec("decode", max_s, batch, "decode")
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, prompt_len), 0, cfg.vocab)

    def on_mesh(c):
        _, sshard, _, pshard = specs_lib.abstract_serve_state(c, shape, mesh)
        with jax.set_mesh(mesh):
            params = _bf16_params(c, seed, out_shardings=pshard)
            tok = NamedSharding(mesh, P())
            kw = {"init": {"out_shardings": sshard.caches},
                  "step": {"in_shardings": (pshard, tok, sshard.caches),
                           "out_shardings": (None, sshard.caches)}}
            per_chip = max(s.data.nbytes for leaf in jax.tree.leaves(params)
                           for s in leaf.addressable_shards[:1])
            logits = _decode_logits(params, c, tokens, max_s, jit_kw=kw)
            held = {d.id for leaf in jax.tree.leaves(params)
                    for d in leaf.devices()}
            return np.asarray(logits, np.float32), held, per_chip

    full_logits, held, per_chip = on_mesh(cfg)
    n_params = cfg.param_count()
    _check(bool(np.isfinite(full_logits).all()),
           f"{cfg.name} logits on the mesh are not finite")
    _check(held == {d.id for d in mesh.devices.flat},
           f"parameters held on {sorted(held)}")
    small = dataclasses.replace(cfg, n_layers=small_layers)
    mesh_logits, _, _ = on_mesh(small)
    with jax.default_device(jax.devices()[0]):
        one_logits = np.asarray(_decode_logits(
            _bf16_params(small, seed), small, tokens, max_s), np.float32)
    rel = _rel_rms(mesh_logits, one_logits)
    bound = decode_bound(small_layers)
    print(f"  sharded {cfg.name}: {n_params} params (bf16) on a "
          f"(1, {model_axis}) mesh, largest shard {per_chip} B, "
          f"{prompt_len} decode steps finite; {small_layers} layers mesh "
          f"vs one device rel RMS {rel:.3g} (bound {bound:.3g})", flush=True)
    _check(rel <= bound, f"{small_layers}-layer logits differ between the "
                         f"mesh and one device: {rel:.3g} > {bound:.3g}")
    return {"params": n_params, "rel_rms": rel, "bound": bound}


# -------------------------------------------------------------------- main --

def _device_line(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def _run(name: str, fn) -> bool:
    t0 = time.perf_counter()
    try:
        fn()
    except Exception as e:  # report and go on: every phase gets its line
        traceback.print_exc()
        print(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s: "
              f"{type(e).__name__}: {e}", flush=True)
        return False
    print(f"[{name}] ok in {time.perf_counter() - t0:.1f}s", flush=True)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip paths (sharded sweep, "
                         "glm4_9b on a (1, 4) mesh)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU device(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2

    from repro.runtime.compile_cache import configure_compilation_cache
    cache = configure_compilation_cache()
    print(f"device: {_device_line(devices)}; compile cache {cache}",
          flush=True)

    if args.four_chips:
        phases = [("sharded-sweep", phase_sharded_sweep),
                  ("sharded-glm4", phase_sharded_model)]
        devices = devices[:4]
    else:
        phases = [("planner", phase_planner), ("sweep", phase_sweep),
                  ("serve", phase_serve)]
        devices = devices[:1]
    # Fresh caches: a stale atlas or profile would turn the sweep into
    # measured=0 or change what the planner ranks with.
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as scratch:
        os.environ["REPRO_PROFILE_DIR"] = str(Path(scratch, "profiles"))
        os.environ["REPRO_ATLAS_DIR"] = str(Path(scratch, "atlas"))
        ok = all([_run(name, fn) for name, fn in phases])
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": _device_line(devices)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
