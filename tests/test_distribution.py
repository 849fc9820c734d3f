"""Distribution layer: sharding rules, data pipeline determinism,
serve/generate consistency, HLO collective parsing, small-mesh train."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.hlo import Roofline, collective_stats
from repro.sharding.rules import batch_spec, params_specs, spec_for


def mk_mesh():
    n = jax.device_count()
    if n < 2:
        pytest.skip("needs >= 2 local devices")
    return jax.make_mesh((n // 2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


# ------------------------------------------------------------- rules -----

def test_spec_for_tp_and_fsdp():
    mesh = mk_mesh()
    s = spec_for(("embed", "ffn"), (64, 128), mesh)
    assert s == P("data", "model")


def test_spec_for_divisibility_guard():
    mesh = mk_mesh()
    # 7 not divisible by model size → replicated on that dim
    s = spec_for(("embed", "ffn"), (64, 7), mesh)
    assert s == P("data")


def test_spec_for_no_double_axis_use():
    mesh = mk_mesh()
    s = spec_for(("ffn", "heads"), (64, 64), mesh)
    # both want "model"; only the first gets it
    assert s == P("model")


def test_params_specs_cover_model():
    from repro.configs import get_smoke
    from repro.models import api
    mesh = mk_mesh()
    cfg = get_smoke("glm4_9b")
    params, axes = api.init(jax.random.PRNGKey(0), cfg)
    specs = params_specs(axes, params, mesh)
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)


def test_batch_spec_axes():
    mesh = mk_mesh()
    assert batch_spec(mesh) == P("data")


# ---------------------------------------------------------- pipeline -----

def test_pipeline_determinism_across_dp_resize():
    """Global sample ids make the stream invariant to dp_size (elastic)."""
    from repro.data.pipeline import SyntheticLM
    a = SyntheticLM(1000, 16, 8, dp_rank=0, dp_size=1, seed=3)
    b0 = SyntheticLM(1000, 16, 8, dp_rank=0, dp_size=2, seed=3)
    b1 = SyntheticLM(1000, 16, 8, dp_rank=1, dp_size=2, seed=3)
    full = a.batch_at(5)["tokens"]
    half0 = b0.batch_at(5)["tokens"]
    half1 = b1.batch_at(5)["tokens"]
    np.testing.assert_array_equal(full, np.concatenate([half0, half1]))


def test_pipeline_batch_at_reproducible():
    from repro.data.pipeline import SyntheticLM
    src = SyntheticLM(500, 8, 4, seed=1)
    np.testing.assert_array_equal(src.batch_at(9)["tokens"],
                                  src.batch_at(9)["tokens"])


def test_prefetcher_yields_in_order():
    from repro.data.pipeline import Prefetcher, SyntheticLM
    src = SyntheticLM(100, 4, 2, seed=0)
    pf = Prefetcher(src, start_step=3, depth=2)
    try:
        for want in (3, 4, 5):
            step, batch = next(pf)
            assert step == want
            np.testing.assert_array_equal(batch["tokens"],
                                          src.batch_at(want)["tokens"])
    finally:
        pf.close()


def test_memmap_source(tmp_path):
    from repro.data.pipeline import MemmapLM
    toks = np.arange(10000, dtype=np.uint32)
    path = str(tmp_path / "toks.bin")
    toks.tofile(path)
    src = MemmapLM(path, vocab=50000, seq_len=16, global_batch=4, seed=0)
    b = src.batch_at(0)
    assert b["tokens"].shape == (4, 16)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])


# -------------------------------------------------------------- serve ----

def test_generate_greedy_consistency():
    """generate() then teacher-forced forward agree on the argmax path."""
    from repro.configs import get_smoke
    from repro.models import api
    from repro.serve.decode import generate
    cfg = get_smoke("yi_9b")
    params, _ = api.init(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray([[5, 9, 2]], jnp.int32)
    out = generate(params, cfg, prompt, max_new=4, max_s=16)
    assert out.shape == (1, 7)
    # re-score: feeding out[:, :-1] must predict out[:, -1] greedily
    batch = {"tokens": out[:, :-1], "labels": out[:, :-1]}
    logits, _ = api.forward_train(params, cfg, batch)
    nxt = int(jnp.argmax(logits[0, -1]))
    assert nxt == int(out[0, -1])


# ---------------------------------------------------------------- hlo ----

HLO_SAMPLE = """
HloModule test
ENTRY main {
  %p0 = f32[128,256]{1,0} parameter(0)
  %ag = f32[512,256]{1,0} all-gather(%p0), replica_groups={}, dimensions={0}
  %ar = f32[128,256]{1,0} all-reduce(%p0), to_apply=%sum
  %rs = bf16[64,256]{1,0} reduce-scatter(%p0), dimensions={0}
  ROOT %cp = f32[128,256]{1,0} collective-permute(%ar)
}
"""


def test_collective_stats_parses_ops_and_bytes():
    st = collective_stats(HLO_SAMPLE)
    assert st.counts == {"all-gather": 1, "all-reduce": 1,
                         "reduce-scatter": 1, "collective-permute": 1}
    assert st.bytes_["all-gather"] == 512 * 256 * 4
    assert st.bytes_["reduce-scatter"] == 64 * 256 * 2
    assert st.total_count == 4


def test_roofline_terms_and_bottleneck():
    r = Roofline(flops_per_device=197e12, bytes_per_device=819e9 * 2,
                 collective_bytes=0.0, chips=256)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(2.0)
    assert r.bottleneck == "memory"
    assert r.t_bound == pytest.approx(2.0)
    # useful-flops roofline fraction
    frac = r.roofline_fraction(model_flops_total=197e12 * 256)
    assert frac == pytest.approx(0.5)


# --------------------------------------------------- tiny-mesh training ---

def test_sharded_train_step_runs_on_host_mesh():
    from repro.configs import get_smoke
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.context import activation_sharding
    from repro.train.train_step import make_train_state, make_train_step
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 local devices")
    mesh = make_host_mesh(model=2)
    cfg = get_smoke("olmoe_1b_7b")
    with jax.set_mesh(mesh), activation_sharding(mesh):
        state, _ = make_train_state(jax.random.PRNGKey(0), cfg)
        src = SyntheticLM(cfg.vocab, 32, 4)
        batch = {k: jnp.asarray(v) for k, v in src.batch_at(0).items()}
        step = jax.jit(make_train_step(cfg))
        state, m1 = step(state, batch)
        state, m2 = step(state, batch)
    assert np.isfinite(float(m1["loss"])) and np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) < float(m1["loss"]) + 1.0


def test_scan_util_unrolled_matches_loop():
    from repro.models import scan_util

    def body(c, x):
        return c + x, c * 2

    xs = jnp.arange(6, dtype=jnp.float32)
    c1, y1 = jax.lax.scan(body, jnp.float32(0), xs)
    with scan_util.unrolled():
        c2, y2 = scan_util.scan(body, jnp.float32(0), xs)
    assert float(c1) == float(c2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))
