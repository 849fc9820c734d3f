import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init). 512 host-platform placeholder devices back both the
single-pod (16×16) and multi-pod (2×16×16) production meshes.

Per cell this driver:
  1. builds ShapeDtypeStruct inputs + NamedShardings (launch/specs.py),
     with sequence-parallel activation constraints active,
  2. ``jax.jit(step, in_shardings, out_shardings).lower(...).compile()``,
  3. records ``memory_analysis()`` (fits-per-device proof) and the HLO
     collective schedule (launch/hlo.py),
  4. **depth-corrects** FLOPs/bytes/collective-bytes: XLA cost analysis
     counts a ``while`` (lax.scan over layers) body ONCE, so the driver
     compiles two shallow depth variants of the same cell and linearly
     extrapolates to the full depth — exact for uniform stacks
     (hybrid's tail remainder ≈5% approximation, documented).

Usage:
  python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod both --out dryrun.json
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, get, normalize, shape_applicable
from repro.launch import hlo as hlo_lib
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import ModelConfig
from repro.serve.decode import make_serve_step
from repro.sharding.context import activation_sharding
from repro.train.train_step import make_train_step


def _cfg_for_cell(arch: str, shape_name: str) -> ModelConfig:
    cfg = get(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        # Big-model training cells need per-layer remat to bound
        # activation memory at seq 4096 × batch 256.
        cfg = dataclasses.replace(cfg, remat="full")
    return cfg


def _depth_unit(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid" and cfg.attn_every:
        return cfg.attn_every
    if cfg.window_pattern:
        return len(cfg.window_pattern)
    return 1


def _depth_variant(cfg: ModelConfig, k: int) -> ModelConfig:
    kw: Dict[str, Any] = {"n_layers": k}
    if cfg.family == "encdec":
        kw["encoder_layers"] = max(1, min(cfg.encoder_layers, k))
    return dataclasses.replace(cfg, **kw)


def lower_cell(cfg: ModelConfig, shape_name: str, multi_pod: bool,
               donate: bool = True, policy: str = "auto"):
    """Lower+compile one cell; returns (compiled, meta dict)."""
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)

    with jax.set_mesh(mesh), activation_sharding(
            mesh, heads=not multi_pod):
        if shape.kind == "train":
            state, state_shard = specs_lib.abstract_train_state(
                cfg, mesh, policy=policy)
            batch, batch_shard = specs_lib.abstract_batch(cfg, shape, mesh)
            step = make_train_step(cfg, accum_steps=1)
            jitted = jax.jit(
                step,
                in_shardings=(state_shard, batch_shard),
                out_shardings=(state_shard, None),
                donate_argnums=(0,) if donate else (),
            )
            lowered = jitted.lower(state, batch)
        elif shape.kind == "prefill":
            pshapes, _, pshard = specs_lib.param_shardings(
                cfg, mesh, jnp.bfloat16)
            batch, batch_shard = specs_lib.abstract_batch(cfg, shape, mesh)
            if cfg.family in ("encdec", "hybrid"):
                # prefill == teacher-forced forward for these families.
                from repro.models import api

                def step(params, b):
                    logits, _ = api.forward_train(params, cfg, b)
                    return logits

                jitted = jax.jit(step, in_shardings=(pshard, batch_shard))
                lowered = jitted.lower(pshapes, batch)
            else:
                serve_state, sshard, _, _ = specs_lib.abstract_serve_state(
                    cfg, shape, mesh)
                from repro.models import api

                def step(params, b, caches):
                    return api.prefill(params, cfg, b, caches)

                jitted = jax.jit(
                    step,
                    in_shardings=(pshard, batch_shard, sshard.caches),
                    out_shardings=(None, sshard.caches),
                    donate_argnums=(2,) if donate else (),
                )
                lowered = jitted.lower(pshapes, batch, serve_state.caches)
        else:  # decode
            serve_state, sshard, pshapes, pshard = \
                specs_lib.abstract_serve_state(cfg, shape, mesh)
            step = make_serve_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(sshard, pshard),
                out_shardings=(sshard, sshard.last_tokens),
                donate_argnums=(0,) if donate else (),
            )
            lowered = jitted.lower(serve_state, pshapes)

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    meta = {"compile_s": compile_s,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "chips": 512 if multi_pod else 256,
            "kind": shape.kind}
    return compiled, meta


def _cell_costs(compiled) -> Tuple[float, float, float, Dict]:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bts = float(ca.get("bytes accessed", 0.0))
    coll = hlo_lib.collective_stats(compiled.as_text())
    return flops, bts, float(coll.total_bytes), {
        "counts": coll.counts, "bytes": coll.bytes_}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             depth_correct: bool = True) -> Dict[str, Any]:
    cfg = _cfg_for_cell(arch, shape_name)
    shape = SHAPES[shape_name]
    run, why = shape_applicable(cfg, shape)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    if not run:
        return {"arch": cfg.name, "shape": shape_name, "mesh": mesh_tag,
                "skipped": True, "reason": why}

    compiled, meta = lower_cell(cfg, shape_name, multi_pod)
    mem = compiled.memory_analysis()
    flops_raw, bytes_raw, coll_raw, coll_detail = _cell_costs(compiled)

    # ---- depth correction --------------------------------------------
    # XLA cost analysis counts while bodies once, so FLOPs/bytes/collective
    # totals come from *unrolled* shallow variants (scan_util.unrolled):
    # two depths → exact per-layer increment → linear extrapolation.
    from repro.models import scan_util
    unit = _depth_unit(cfg)
    L = cfg.n_layers
    if depth_correct and L > 2 * unit:
        with scan_util.unrolled():
            c1, _ = lower_cell(_depth_variant(cfg, unit), shape_name,
                               multi_pod, donate=False)
            c2, _ = lower_cell(_depth_variant(cfg, 2 * unit), shape_name,
                               multi_pod, donate=False)
        f1, b1, l1, _ = _cell_costs(c1)
        f2, b2, l2, _ = _cell_costs(c2)
        scale = (L - unit) / unit
        flops = f1 + scale * max(0.0, f2 - f1)
        bts = b1 + scale * max(0.0, b2 - b1)
        coll = l1 + scale * max(0.0, l2 - l1)
        depth_note = (f"depth-corrected (unrolled L={unit},{2*unit} "
                      f"variants, linear in depth)")
    else:
        with scan_util.unrolled():
            cu, _ = lower_cell(cfg, shape_name, multi_pod, donate=False)
        flops, bts, coll, _ = _cell_costs(cu)
        depth_note = "direct (fully unrolled shallow model)"

    chips = meta["chips"]
    roof = hlo_lib.Roofline(
        flops_per_device=flops, bytes_per_device=bts,
        collective_bytes=coll, chips=chips)
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if meta["kind"] != "decode"
                                   else 1)
    mult = 6 if meta["kind"] == "train" else 2
    model_flops = mult * n_active * tokens
    return {
        "arch": cfg.name,
        "shape": shape_name,
        "mesh": meta["mesh"],
        "kind": meta["kind"],
        "compile_s": round(meta["compile_s"], 1),
        "params": n_params,
        "active_params": n_active,
        "depth_note": depth_note,
        "bytes_per_device": {
            "args": mem.argument_size_in_bytes,
            "out": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "alias": mem.alias_size_in_bytes,
            "peak_est": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bts,
        "collectives": coll_detail,
        "collective_bytes": coll,
        "t_compute": roof.t_compute,
        "t_memory": roof.t_memory,
        "t_collective": roof.t_collective,
        "bottleneck": roof.bottleneck,
        "model_flops": model_flops,
        "model_flops_ratio": roof.model_flops_ratio(model_flops),
        "roofline_fraction": roof.roofline_fraction(model_flops),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=("off", "on", "both"),
                    default="off")
    ap.add_argument("--no-depth-correct", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    archs = ARCH_IDS if args.all or not args.arch else (
        normalize(args.arch),)
    shapes = tuple(SHAPES) if args.all or not args.shape else (args.shape,)
    pods = {"off": (False,), "on": (True,), "both": (False, True)}[
        args.multi_pod]

    results = []
    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in pods:
                tag = f"{arch} × {shape_name} × {'2x16x16' if mp else '16x16'}"
                try:
                    r = run_cell(arch, shape_name, mp,
                                 depth_correct=not args.no_depth_correct)
                    results.append(r)
                    if r.get("skipped"):
                        print(f"[skip] {tag}: {r['reason']}", flush=True)
                    else:
                        print(
                            f"[ ok ] {tag}: compile={r['compile_s']}s "
                            f"peak={r['bytes_per_device']['peak_est']/2**30:.2f}GiB "
                            f"tc={r['t_compute']*1e3:.2f}ms "
                            f"tm={r['t_memory']*1e3:.2f}ms "
                            f"tl={r['t_collective']*1e3:.2f}ms "
                            f"→ {r['bottleneck']} "
                            f"roofline={r['roofline_fraction']:.2%}",
                            flush=True)
                except Exception as e:
                    failures += 1
                    traceback.print_exc()
                    print(f"[FAIL] {tag}: {type(e).__name__}: {e}",
                          flush=True)
                    results.append({"arch": arch, "shape": shape_name,
                                    "mesh": "2x16x16" if mp else "16x16",
                                    "error": f"{type(e).__name__}: {e}"})
                if args.out:
                    # incremental write: partial sweeps still produce
                    # a usable artifact (atomic rename).
                    with open(args.out + ".tmp", "w") as f:
                        json.dump(results, f, indent=1)
                    os.replace(args.out + ".tmp", args.out)
    if args.out:
        print(f"wrote {args.out}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
