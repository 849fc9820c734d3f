"""Compile the timed path of ``aatb.ns_widths`` for a described v5e chip.

Nothing runs. Every algorithm of every Newton–Schulz shape is compiled
through the backend-built callable (the ``ops.syrk`` / ``ops.symm`` /
``ops.gemm`` wrappers and their Pallas kernels) for one chip of a
described ``v5e:2x2``, with the cell's operand maker and reference; the
TPU compiler refuses a kernel over its VMEM limit or a program over the
chip's memory. The topology is described inside a fixture, never at
import, as only one process may load the TPU library.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import ROOT

BF16 = jnp.bfloat16


def _ns_shapes():
    traffic = json.loads(
        (ROOT / "chipbench" / "traffic" / "ns_widths.json").read_text())
    return [tuple(i["dims"]) for i in traffic["instances"]]


@pytest.fixture(scope="module")
def one_chip():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU compiler (libtpu) is not installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
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
def mosaic(monkeypatch, one_chip):
    """Steer the kernel wrappers to Mosaic: this process's backend is the
    CPU, where they would pick interpret mode."""
    from repro.core.perfmodel import hardware_spec
    from repro.kernels import ops

    kind = one_chip._device.device_kind
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "_vmem_budget",
                        lambda: hardware_spec(kind).vmem_bytes)
    jax.clear_caches()
    yield one_chip
    jax.clear_caches()


def compile_cell_shapes(expression, dims, sharding):
    """Compile every algorithm at ``dims`` and the reference; the number
    of Mosaic kernels in each algorithm's program."""
    from chipbench import check
    from chipbench.harness import load_module
    from repro.core.backends import make_backend, num_inputs
    from repro.core.expressions import get_spec

    expr = load_module(ROOT, "expressions", expression)
    runner = make_backend("pallas", dtype="bfloat16")
    shapes = expr.operand_shapes(dims)
    ops = {b: jax.ShapeDtypeStruct(s, BF16, sharding=sharding)
           for b, s in shapes.items()}
    kernels = {}
    for alg in get_spec(expression).algorithms(dims):
        args = [ops.get(i, ops[min(ops)]) for i in range(num_inputs(alg))]
        compiled = jax.jit(runner.build(alg)).lower(*args).compile()
        kernels[alg.name] = compiled.as_text().count(
            'custom_call_target="tpu_custom_call"')
    jax.jit(lambda o: check.reference(expr, o)).lower(ops).compile()
    return kernels


@pytest.mark.parametrize("dims", _ns_shapes(), ids=str)
def test_ns_shapes_compile_for_v5e(mosaic, dims):
    kernels = compile_cell_shapes("aatb", dims, mosaic)
    assert len(kernels) == 5
    assert all(n >= 1 for n in kernels.values()), kernels
