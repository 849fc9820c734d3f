"""``chip_smoke.py`` on the CPU: every phase at smoke sizes, and the refusal.

On the chip the phases run at full width; here they run the same code at
sizes the CPU finishes in seconds (Pallas in interpret mode). The script
itself must refuse to run, and print no verdict, without a TPU.
"""

import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.configs import get_smoke

ROOT = Path(__file__).resolve().parents[1]
SMALL_CASES = (("aatb", (64, 32, 48)), ("abcd", (32, 48, 64, 40, 32)),
               ("abab", (40, 32, 64)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


chip_smoke = _load()


# XLA:CPU has no bf16 x bf16 -> f32 dot for interpret-mode Pallas, so the
# pallas backend is exercised at float32 here (bf16 runs on the chip).
@pytest.mark.parametrize("backend,dtype", [
    ("jax", "bfloat16"), ("jax", "float32"), ("pallas", "float32")])
def test_planner_phase(backend, dtype):
    out = chip_smoke.phase_planner(cases=SMALL_CASES, backends=(backend,),
                                   dtypes=(dtype,), require_mosaic=False)
    row = out[f"{backend}/{dtype}"]
    assert row["algorithms"] == 5 + 6 + 13
    assert row["worst_err_over_bound"] <= 1.0


def test_sweep_phase():
    line = chip_smoke.phase_sweep(grid="32,64")
    assert "measured=8 " in line and "device='cpu'" in line


def test_serve_phase():
    out = chip_smoke.phase_serve(cfg=get_smoke("phi3_mini"), batch=2,
                                 prompt_len=8, new=4, max_s=32)
    assert out["rel_rms"] <= out["bound"]
    assert out["greedy_agreement"] == 1.0


def test_bounds_follow_unit_roundoff():
    u = 2.0 ** -8
    assert chip_smoke.bf16_bound(3, 4096) == pytest.approx(
        6 * (u + 4096 * 2.0 ** -24))
    assert chip_smoke.decode_bound(32) == pytest.approx(2 * 8 * u)


def test_four_chip_phases_on_virtual_devices():
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        from repro.configs import get_smoke
        s = chip_smoke.phase_sharded_sweep(values=(32, 64), backend="jax")
        assert set(s["operands"]) == set(s["results"]) == {{0, 1, 2, 3}}
        m = chip_smoke.phase_sharded_model(cfg=get_smoke("glm4_9b"),
                                           prompt_len=8, max_s=64)
        assert m["rel_rms"] <= m["bound"]
        print("OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert p.returncode == 0 and "OK" in p.stdout, p.stdout + p.stderr


def test_serve_phase_rejects_wrong_tokens(monkeypatch):
    """Tokens that are not the decode argmax fail the phase."""
    from repro.serve import decode

    real = decode.generate

    def off_by_one(params, cfg, prompt, max_new, max_s):
        out = real(params, cfg, prompt, max_new=max_new, max_s=max_s)
        s0 = prompt.shape[1]
        return out.at[:, s0:].set((out[:, s0:] + 1) % cfg.vocab)

    monkeypatch.setattr(decode, "generate", off_by_one)
    with pytest.raises(chip_smoke.PhaseError, match="not the decode argmax"):
        chip_smoke.phase_serve(cfg=get_smoke("phi3_mini"), batch=2,
                               prompt_len=8, new=4, max_s=32)


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_without_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    assert "{" not in capsys.readouterr().out


def test_compile_cache_placement(monkeypatch, tmp_path):
    """Unset: ``<checkout>/.jax_cache``. Set: JAX's own, nothing set here."""
    import jax

    from repro.runtime import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compilation_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.configure_compilation_cache()
        assert path == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
