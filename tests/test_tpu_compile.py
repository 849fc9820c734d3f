"""Compile the main path's kernels and one decode step for a v5e chip.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a
described ``v5e:2x2`` topology with no chip attached. It refuses what
interpret mode cannot — a kernel over the 16 MiB scoped VMEM limit, a
program over the chip's HBM — so these tests guard every change at no chip
time. The topology is described inside a fixture (only one process may
load the TPU library), never at import.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.perfmodel import hardware_spec
from repro.kernels import chain_gemm as chain_lib
from repro.kernels import ops

BF16 = jnp.bfloat16
F32 = jnp.float32
HBM_V5E = 15.75 * 2 ** 30  # what XLA reports as usable on one v5e chip


@pytest.fixture(scope="module")
def topo():
    """Skips only where the TPU compiler is not installed; any other
    failure to describe the chip (a held libtpu lock among them) fails."""
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU compiler (libtpu) is not installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def mosaic(monkeypatch, topo):
    """Steer ``ops`` to Mosaic and the described chip's VMEM budget.

    This process's own backend is the CPU, so ``ops`` would otherwise pick
    interpret mode; traces made either way must not be reused.
    """
    kind = topo.devices[0].device_kind
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "_vmem_budget",
                        lambda: hardware_spec(kind).vmem_bytes)
    jax.clear_caches()
    yield hardware_spec(kind).vmem_bytes
    jax.clear_caches()


def _spec(shape, sharding, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("tile", [128, 256, 512])
@pytest.mark.parametrize("pipeline", [0, 1])
def test_gemm_compiles(mosaic, one_chip, tile, pipeline):
    n = 2048
    a = _spec((n, n), one_chip)
    f = jax.jit(lambda a, b: ops.gemm(a, b, bm=tile, bn=tile, bk=tile,
                                      pipeline=pipeline))
    assert _kernels(f.lower(a, a).compile()) == 1


@pytest.mark.parametrize("tile", [128, 256, 512])
def test_syrk_and_symm_compile(mosaic, one_chip, tile):
    n = 2048
    a = _spec((n, n), one_chip)
    syrk = jax.jit(lambda a: ops.syrk(a, bm=tile, bk=tile))
    symm = jax.jit(lambda s, b: ops.symm(s, b, bm=tile, bn=tile))
    assert _kernels(syrk.lower(a).compile()) == 1
    assert _kernels(symm.lower(a, a).compile()) == 1


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("m,n", [(3072, 3072), (3072, 8192), (4096, 4096),
                                 (512, 4096), (4096, 11008)], ids=str)
def test_symm_panel_compiles_at_the_newton_schulz_widths(mosaic, one_chip,
                                                         m, n, dtype):
    """At the tiles the backend takes there, the column panel the wrapper
    sizes to the VMEM budget (wider than one block in bfloat16) is one
    kernel the compiler accepts."""
    from repro.core.tuning import shape_config
    cfg = dict(shape_config("symm", (m, n),
                            dtype_bytes=jnp.dtype(dtype).itemsize))
    assert ops.symm_panel_cols(n, dtype, **cfg) > (
        cfg["bn"] if dtype == BF16 else 0)
    symm = jax.jit(lambda s, b: ops.symm(s, b, **cfg))
    compiled = symm.lower(_spec((m, m), one_chip, dtype),
                          _spec((m, n), one_chip, dtype)).compile()
    assert _kernels(compiled) == 1


def _compiles(fn, *args) -> bool:
    try:
        jax.jit(fn).lower(*args).compile()
    except Exception as e:  # the compiler's refusal, named
        assert "vmem" in str(e).lower(), e
        return False
    return True


@pytest.mark.parametrize("m,k,l,n", [
    (1024, 1024, 3072, 1024),   # fused fits
    (1024, 1024, 4096, 1024),   # the l = 4096 instance: refused
    (1024, 2048, 2048, 1024),   # B of 8 MiB held once: fits
    (1024, 2048, 2560, 1024),   # refused
])
def test_chain_gemm_guard_matches_compiler(mosaic, one_chip, m, k, l, n):
    """The guard refuses exactly what Mosaic refuses, and ``ops`` compiles
    either way: one fused kernel, or two GEMM kernels."""
    a, b, c = (_spec(s, one_chip) for s in ((m, k), (k, l), (l, n)))
    need = chain_lib.chain_gemm_vmem_bytes(m, k, l, n, dtype_bytes=2)
    fits = need <= mosaic
    assert _compiles(chain_lib.chain_gemm_pallas, a, b, c) == fits
    compiled = jax.jit(ops.chain_gemm).lower(a, b, c).compile()
    assert _kernels(compiled) == (1 if fits else 2)


@pytest.mark.parametrize("m,k,l", [
    (1024, 1024, 4096),   # fits in bf16
    (1024, 1024, 8192),   # refused
])
def test_gemm_syrk_guard_matches_compiler(mosaic, one_chip, m, k, l):
    a, b = _spec((m, k), one_chip), _spec((k, l), one_chip)
    need = chain_lib.gemm_syrk_vmem_bytes(m, k, l, dtype_bytes=2)
    fits = need <= mosaic
    assert _compiles(chain_lib.gemm_syrk_pallas, a, b) == fits
    compiled = jax.jit(ops.gemm_syrk).lower(a, b).compile()
    assert _kernels(compiled) == (1 if fits else 2)


def test_f32_kernels_compile_at_highest_precision(mosaic, one_chip):
    """float32 dots ask Mosaic for fp32 contract precision (see
    tests/test_backends.py); it accepts them at every kernel."""
    a = _spec((2048, 2048), one_chip, F32)
    for fn, args in ((ops.gemm, (a, a)), (ops.syrk, (a,)),
                     (ops.symm, (a, a))):
        assert _kernels(jax.jit(fn).lower(*args).compile()) == 1, fn


@pytest.mark.parametrize("m,k,l,n", [
    (1024, 1024, 2176, 1024),   # fits in f32
    (1024, 1024, 2432, 1024),   # refused
])
def test_chain_gemm_guard_matches_compiler_f32(mosaic, one_chip, m, k, l, n):
    a, b, c = (_spec(s, one_chip, F32) for s in ((m, k), (k, l), (l, n)))
    need = chain_lib.chain_gemm_vmem_bytes(m, k, l, n, dtype_bytes=4)
    fits = need <= mosaic
    assert _compiles(chain_lib.chain_gemm_pallas, a, b, c) == fits
    compiled = jax.jit(ops.chain_gemm).lower(a, b, c).compile()
    assert _kernels(compiled) == (1 if fits else 2)


@pytest.mark.parametrize("m,k,l", [
    (1024, 1024, 2432),   # fits in f32
    (1024, 1024, 2560),   # refused: fits only at the default precision
])
def test_gemm_syrk_guard_matches_compiler_f32(mosaic, one_chip, m, k, l):
    a, b = _spec((m, k), one_chip, F32), _spec((k, l), one_chip, F32)
    need = chain_lib.gemm_syrk_vmem_bytes(m, k, l, dtype_bytes=4)
    fits = need <= mosaic
    assert _compiles(chain_lib.gemm_syrk_pallas, a, b) == fits
    compiled = jax.jit(ops.gemm_syrk).lower(a, b).compile()
    assert _kernels(compiled) == (1 if fits else 2)


def test_flash_attention_head_dim_96_compiles(mosaic, one_chip):
    q = _spec((1, 32, 2048, 96), one_chip)
    compiled = jax.jit(ops.flash_attention).lower(q, q, q).compile()
    assert _kernels(compiled) == 1


def test_phi3_mini_decode_step_fits_one_chip(one_chip):
    """Full-width phi3-mini, bf16 weights, batch 4, 2,048-token cache."""
    from repro.configs import get
    from repro.models import api
    from repro.serve.decode import ServeState, make_serve_step

    cfg = get("phi3_mini")
    batch, max_s = 4, 2048
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: api.init(k, cfg, BF16)[0], key)
    caches = jax.eval_shape(
        lambda p: api.init_caches(p, cfg, batch, max_s), params)
    place = lambda s: _spec(s.shape, one_chip, s.dtype)  # noqa: E731
    state = ServeState(caches=jax.tree.map(place, caches),
                       last_tokens=_spec((batch, 1), one_chip, jnp.int32),
                       rng=_spec((2,), one_chip, jnp.uint32))
    compiled = jax.jit(make_serve_step(cfg)).lower(
        state, jax.tree.map(place, params)).compile()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 6 * 2 ** 30  # the weights are there
    assert peak <= HBM_V5E, f"{peak / 2 ** 30:.2f} GiB > 15.75 GiB"
