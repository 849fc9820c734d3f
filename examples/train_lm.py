"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps.

A mamba2-family model sized to ~100M params (the paper's planner picks the
SSD form per shape; Muon orthogonalizes the 2-D weights via the planned
AAᵀB chains). Checkpoints + crash-resume supervisor included — kill the
process mid-run and rerun to watch it resume.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse

import jax

from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models.ssm import SSMConfig
from repro.models.transformer import ModelConfig
from repro.runtime.supervisor import RestartPolicy, Supervisor
from repro.train import loop as train_loop


def model_100m() -> ModelConfig:
    """~100M params: 12 layers, d=768 mamba2 (SSD planner active)."""
    return ModelConfig(
        name="mamba2-100m", family="ssm", n_layers=12, d_model=768,
        vocab=50280, tied_embeddings=True,
        ssm=SSMConfig(d_model=768, d_inner=1536, n_heads=24, head_dim=64,
                      n_groups=1, d_state=64, conv_kernel=4, chunk=64,
                      ssd_mode="auto", discriminant="perfmodel"),
        max_seq=2048,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--optimizer", default="muon",
                    choices=("adamw", "muon"))
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = model_100m()
    n_params = sum(
        x.size for x in jax.tree.leaves(
            __import__("repro.models.api", fromlist=["api"]).init(
                jax.random.PRNGKey(0), cfg)[0]))
    print(f"model: {cfg.name} — {n_params/1e6:.1f}M params, "
          f"optimizer={args.optimizer}")

    src = SyntheticLM(cfg.vocab, args.seq, args.batch, seed=0)
    mesh = make_host_mesh()

    def run(attempt):
        with jax.set_mesh(mesh):
            return train_loop.train(
                cfg, src, args.steps, ckpt_dir=args.ckpt, save_every=50,
                optimizer=args.optimizer, peak_lr=3e-4, warmup=20,
                log_every=10, mesh=mesh)

    state = Supervisor(RestartPolicy(max_restarts=3)).run(run)
    print(f"finished at step {int(jax.device_get(state.step))}")


if __name__ == "__main__":
    main()
