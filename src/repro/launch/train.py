"""End-to-end training driver (runs on real local devices).

  PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m \
      --smoke --steps 50 --ckpt /tmp/ckpt

``--smoke`` uses the reduced config (CPU-feasible); without it the full
assigned config is used (requires TPU-scale memory). The driver wires the
synthetic data pipeline, mesh, sharding rules, checkpoint manager and
supervisor together — the same path the dry-run proves at 512 devices.
"""

from __future__ import annotations

import argparse
import sys

import jax

from repro.configs import get, get_smoke, normalize
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.runtime.supervisor import RestartPolicy, Supervisor
from repro.sharding.context import activation_sharding
from repro.train import loop as train_loop


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--optimizer", default="adamw",
                    choices=("adamw", "muon"))
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--max-restarts", type=int, default=2)
    args = ap.parse_args(argv)

    cfg = get_smoke(args.arch) if args.smoke else get(normalize(args.arch))
    mesh = make_host_mesh(model=args.model_parallel)

    extra = {}
    if cfg.family == "encdec":
        extra["frames"] = ((cfg.encoder_seq, cfg.d_model), "float32")
    if cfg.family == "vlm":
        extra["vision_embeds"] = ((cfg.vision_tokens, cfg.d_model),
                                  "float32")
    source = SyntheticLM(cfg.vocab, args.seq, args.batch,
                         extra_specs=extra)

    def run(attempt: int):
        with jax.set_mesh(mesh), activation_sharding(mesh):
            return train_loop.train(
                cfg, source, args.steps, ckpt_dir=args.ckpt,
                optimizer=args.optimizer, peak_lr=args.lr, mesh=mesh)

    sup = Supervisor(RestartPolicy(max_restarts=args.max_restarts,
                                   backoff_s=0.1))
    state = sup.run(run)
    print(f"[train] done at step {int(jax.device_get(state.step))}; "
          f"restarts={sup.restarts}")
    return 0


if __name__ == "__main__":
    from repro.runtime.compile_cache import configure_compilation_cache
    configure_compilation_cache()
    sys.exit(main())
