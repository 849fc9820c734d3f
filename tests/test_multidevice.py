"""Multi-device behaviours, exercised in subprocesses.

The main pytest session keeps the default single CPU device (per project
policy — forcing host devices globally would distort smoke tests), so
anything needing a real mesh runs as a child python with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import os
import subprocess
import sys
import textwrap

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_child(code: str, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO_SRC
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return p.stdout


def test_sharded_train_step_8dev():
    out = run_child("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.data.pipeline import SyntheticLM
        from repro.launch.mesh import make_host_mesh
        from repro.sharding.context import activation_sharding
        from repro.train.train_step import make_train_state, make_train_step
        assert jax.device_count() == 8
        mesh = make_host_mesh(model=2)
        cfg = get_smoke("glm4_9b")
        with jax.set_mesh(mesh), activation_sharding(mesh):
            state, _ = make_train_state(jax.random.PRNGKey(0), cfg)
            src = SyntheticLM(cfg.vocab, 32, 8)
            batch = {k: jnp.asarray(v) for k, v in src.batch_at(0).items()}
            step = jax.jit(make_train_step(cfg))
            losses = []
            for i in range(4):
                batch = {k: jnp.asarray(v)
                         for k, v in src.batch_at(i).items()}
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
        assert all(np.isfinite(l) for l in losses), losses
        assert losses[-1] < losses[0], losses
        print("OK", losses)
    """)
    assert "OK" in out


def test_elastic_checkpoint_reshard_8dev(tmp_path):
    out = run_child(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import store
        mesh1 = jax.make_mesh((4,), ("data",),
                              axis_types=(jax.sharding.AxisType.Auto,))
        arr = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        tree = {{"w": jax.device_put(
            arr, NamedSharding(mesh1, P("data", None)))}}
        store.save({str(tmp_path)!r}, 1, tree,
                   specs={{"w": P("data", None)}},
                   mesh_shape={{"data": 4}})
        # restore onto a 2x4 mesh (elastic re-mesh)
        mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                              axis_types=(jax.sharding.AxisType.Auto,)*2)
        out = store.restore({str(tmp_path)!r}, 1, tree, mesh=mesh2)
        assert np.array_equal(np.asarray(out["w"]), np.asarray(arr))
        shard_shapes = {{d.index for d in out["w"].addressable_shards}}
        print("OK", len(shard_shapes))
    """)
    assert "OK" in out


def test_tiny_mesh_dryrun_roofline_8dev():
    """End-to-end mini dry-run: proxy config, 4x2 mesh, roofline terms."""
    out = run_child("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.configs.base import ShapeSpec
        from repro.launch import specs as specs_lib
        from repro.launch.hlo import Roofline, collective_stats
        from repro.models.transformer import ModelConfig
        from repro.sharding.context import activation_sharding
        from repro.train.train_step import make_train_step
        from repro.models import scan_util

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = ModelConfig(name="proxy", family="dense", n_layers=2,
                          d_model=256, n_heads=4, n_kv_heads=2,
                          head_dim=64, d_ff=512, vocab=4096,
                          tied_embeddings=True, remat="full")
        shape = ShapeSpec("t", 512, 8, "train")
        with jax.set_mesh(mesh), activation_sharding(mesh), \
                scan_util.unrolled():
            state, sshard = specs_lib.abstract_train_state(cfg, mesh)
            batch, bshard = specs_lib.abstract_batch(cfg, shape, mesh)
            step = make_train_step(cfg)
            compiled = jax.jit(
                step, in_shardings=(sshard, bshard),
                out_shardings=(sshard, None)).lower(state, batch).compile()
        ca = compiled.cost_analysis()
        st = collective_stats(compiled.as_text())
        r = Roofline(flops_per_device=ca["flops"],
                     bytes_per_device=ca["bytes accessed"],
                     collective_bytes=st.total_bytes, chips=8)
        assert r.t_compute > 0 and r.t_memory > 0
        assert st.total_count > 0, "expected collectives in sharded step"
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes > 0
        print("OK", r.bottleneck, st.total_count)
    """)
    assert "OK" in out


def test_serve_step_sharded_8dev():
    out = run_child("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.configs.base import ShapeSpec
        from repro.launch import specs as specs_lib
        from repro.models.transformer import ModelConfig
        from repro.serve.decode import make_serve_step

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = ModelConfig(name="proxy", family="dense", n_layers=2,
                          d_model=128, n_heads=4, n_kv_heads=2,
                          head_dim=32, d_ff=256, vocab=2048,
                          tied_embeddings=True)
        shape = ShapeSpec("d", 256, 8, "decode")
        with jax.set_mesh(mesh):
            st, sshard, pshapes, pshard = \
                specs_lib.abstract_serve_state(cfg, shape, mesh)
            step = make_serve_step(cfg)
            compiled = jax.jit(
                step, in_shardings=(sshard, pshard),
                out_shardings=(sshard, sshard.last_tokens)
            ).lower(st, pshapes).compile()
        print("OK", compiled.memory_analysis().temp_size_in_bytes >= 0)
    """)
    assert "OK" in out
