"""Autotuner tests: search space, roofline pruning, persistence, dispatch.

The load-bearing guarantee (ISSUE-9 acceptance): any candidate whose VMEM
estimate exceeds the hardware budget is rejected by the pre-filter and
*no timing is ever spent on it* — asserted with a spy backend that
records every config reaching the timer.
"""

import json

import numpy as np
import pytest

from repro.core.backends.jax_backend import PallasBackend, PallasOps
from repro.core.perfmodel import HardwareSpec, RooflineProfile
from repro.core.profile_store import (
    FingerprintMismatchError,
    HardwareFingerprint,
    SchemaVersionError,
)
from repro.core.tuning import (
    ALLOWED_KEYS,
    BLOCK_CHOICES,
    DEFAULT_CONFIGS,
    TUNABLE_KINDS,
    TunedEntry,
    TuningTable,
    candidate_configs,
    kernel_vmem_bytes,
    load_default_tuning_table,
    load_tuning_table,
    modeled_time,
    padded_dims,
    prune_candidates,
    save_tuning_table,
    tuning_path,
)
from repro.kernels.autotune import autotune_request, default_tune_requests

FP = HardwareFingerprint(backend="pallas", device="testdev", dtype="float32")


def small_vmem_profile(vmem_bytes: int) -> RooflineProfile:
    return RooflineProfile(HardwareSpec(
        name="test", peak_flops=1e12, hbm_bw=1e11, link_bw=1e9,
        vmem_bytes=vmem_bytes))


# -------------------------------------------------------- search space ---

def test_candidate_spaces_cover_the_block_cross_product():
    n = len(BLOCK_CHOICES)
    assert len(candidate_configs("gemm", (512, 512, 512))) == n ** 3
    assert len(candidate_configs("syrk", (512, 512))) == n ** 2
    assert len(candidate_configs("symm", (512, 512))) == n ** 2
    assert len(candidate_configs("chain_gemm", (512,) * 4)) == n ** 4
    assert len(candidate_configs("gemm_syrk", (512,) * 3)) == n ** 2


def test_tri2full_is_not_tunable():
    assert "tri2full" not in TUNABLE_KINDS
    with pytest.raises(ValueError, match="not tunable"):
        candidate_configs("tri2full", (256,))


def test_padded_dims_quantize_to_blocks():
    assert padded_dims("gemm", (129, 257, 100),
                       {"bm": 256, "bn": 128, "bk": 128}) == (256, 384, 128)
    assert padded_dims("syrk", (64, 64), {"bm": 128, "bk": 128}) == (128, 128)


# ------------------------------------------------- the VMEM guarantee ---

def test_vmem_over_budget_is_rejected_before_any_timing():
    """The acceptance-criterion test: over-budget candidates are pruned
    with reason "vmem" and provably never reach the timer."""
    budget = 600_000  # fits 128-edge gemm tiles (~460 KB); rejects larger
    profile = small_vmem_profile(budget)
    dims = (512, 512, 512)
    report = prune_candidates("gemm", dims, profile=profile, dtype_bytes=4)

    vmem_rejected = [r for r in report.rejected if r.reason == "vmem"]
    assert vmem_rejected, "expected over-budget candidates on this profile"
    for r in vmem_rejected:
        assert kernel_vmem_bytes("gemm", dims, r.config,
                                 dtype_bytes=4) > budget
    for cfg in report.survivors:
        assert kernel_vmem_bytes("gemm", dims, cfg, dtype_bytes=4) <= budget

    timed_configs = []

    class SpyBackend(PallasBackend):
        def make_operands(self, alg):
            return {}

        def time_algorithm(self, alg, operands=None, reps=None):
            timed_configs.append(self._config_lookup("gemm", dims))
            return 1.0

    entry = autotune_request(SpyBackend(reps=1), "gemm", dims,
                             profile=profile)
    assert entry.timed == len(timed_configs)
    assert entry.pruned == len(report.rejected)
    for cfg in timed_configs:
        assert cfg is not None
        assert kernel_vmem_bytes("gemm", dims, cfg, dtype_bytes=4) <= budget


def test_fused_kind_vmem_estimates_delegate_to_kernel_estimators():
    from repro.kernels.chain_gemm import (
        chain_gemm_vmem_bytes,
        gemm_syrk_vmem_bytes,
    )
    cfg = dict(DEFAULT_CONFIGS["chain_gemm"])
    assert kernel_vmem_bytes("chain_gemm", (256, 256, 256, 256), cfg,
                             dtype_bytes=4) == chain_gemm_vmem_bytes(
        256, 256, 256, 256, bm=128, bn=128, dtype_bytes=4)
    cfg = dict(DEFAULT_CONFIGS["gemm_syrk"])
    assert kernel_vmem_bytes("gemm_syrk", (256, 256, 256), cfg,
                             dtype_bytes=4) == gemm_syrk_vmem_bytes(
        256, 256, 256, bm=128, dtype_bytes=4)


# ----------------------------------------------------- pruning policy ---

def test_padding_waste_blocks_are_rejected():
    report = prune_candidates("gemm", (64, 64, 64), dtype_bytes=4)
    padded = [r for r in report.rejected if r.reason == "padding"]
    assert padded  # 256/512 blocks on a 64-dim problem are pure padding
    for r in padded:
        assert any(v > 128 for v in r.config.values())
    for cfg in report.survivors:
        assert max(cfg.values()) <= 128


def test_survivors_are_ordered_cheapest_modeled_first():
    profile = RooflineProfile()
    report = prune_candidates("gemm", (1024, 1024, 1024), profile=profile,
                              dtype_bytes=4)
    assert report.modeled == sorted(report.modeled)
    for cfg, t in zip(report.survivors, report.modeled):
        assert modeled_time("gemm", (1024, 1024, 1024), cfg, profile,
                            dtype_bytes=4) == pytest.approx(t)


def test_default_config_always_survives():
    # Even with a survivor cap of 1, the default tiles must be timed so
    # the persisted winner is measured against the status quo.
    report = prune_candidates("gemm", (1024, 1024, 1024), dtype_bytes=4,
                              max_survivors=1)
    defaults = [c for c in report.survivors
                if all(c.get(k, 128) == 128 for k in ("bm", "bn", "bk"))]
    assert defaults, report.survivors


def test_bigger_tiles_model_less_traffic():
    # The arithmetic-intensity lever the pre-filter ranks by: doubling bn
    # halves A-panel re-streaming, so modeled time must not increase.
    from repro.core.tuning import traffic_elems
    dims = (2048, 2048, 2048)
    small = traffic_elems("gemm", dims, {"bm": 128, "bn": 128, "bk": 128})
    big = traffic_elems("gemm", dims, {"bm": 256, "bn": 256, "bk": 128})
    assert big < small


# -------------------------------------------------------- persistence ---

def test_tuning_table_round_trips(tmp_path):
    table = TuningTable()
    table.set("gemm", (256, 256, 256), TunedEntry(
        config={"bm": 256, "bn": 128, "bk": 128, "pipeline": 1},
        seconds=1e-4, default_seconds=2e-4, timed=5, pruned=22))
    table.set("chain_gemm", (128, 128, 128, 128), TunedEntry(
        config={"bm": 128, "bn": 128, "bk": 128, "bl": 128},
        seconds=3e-4, default_seconds=3e-4, timed=1, pruned=15))
    path = save_tuning_table(table, FP, directory=tmp_path,
                             meta={"grid": "test"})
    assert path == tuning_path(FP, tmp_path)
    loaded, fp = load_tuning_table(path, expected_fingerprint=FP)
    assert fp == FP
    assert len(loaded) == 2
    entry = loaded.entry("gemm", (256, 256, 256))
    assert entry.config == {"bm": 256, "bn": 128, "bk": 128, "pipeline": 1}
    assert entry.seconds == pytest.approx(1e-4)
    assert entry.default_seconds == pytest.approx(2e-4)
    assert (entry.timed, entry.pruned) == (5, 22)
    assert loaded.meta["grid"] == "test"


def test_tuning_table_rejects_wrong_fingerprint(tmp_path):
    path = save_tuning_table(TuningTable(), FP, directory=tmp_path)
    other = HardwareFingerprint(backend="pallas", device="elsewhere",
                                dtype="float32")
    with pytest.raises(FingerprintMismatchError):
        load_tuning_table(path, expected_fingerprint=other)


def test_tuning_table_rejects_wrong_schema(tmp_path):
    path = save_tuning_table(TuningTable(), FP, directory=tmp_path)
    doc = json.loads(path.read_text())
    doc["version"] = 99
    path.write_text(json.dumps(doc))
    with pytest.raises(SchemaVersionError):
        load_tuning_table(path)


def test_nearest_config_fallback_in_log_dim_space():
    table = TuningTable()
    table.set("gemm", (128, 128, 128), TunedEntry(
        config={"bm": 128, "bn": 128, "bk": 128}, seconds=1.0,
        default_seconds=1.0, timed=1, pruned=0))
    table.set("gemm", (2048, 2048, 2048), TunedEntry(
        config={"bm": 512, "bn": 512, "bk": 128}, seconds=1.0,
        default_seconds=1.0, timed=1, pruned=0))
    # Near the big entry → borrows its tiles; near the small one → 128s.
    assert table.config("gemm", (1500, 1800, 2000))["bm"] == 512
    assert table.config("gemm", (150, 100, 128))["bm"] == 128
    # Unknown kind/arity → None (kernel defaults apply).
    assert table.config("syrk", (256, 256)) is None


def test_kill_switch_disables_auto_load(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PROFILE_DIR", str(tmp_path))
    from repro.core.profile_store import current_fingerprint
    fp = current_fingerprint(backend="pallas", dtype="float32")
    table = TuningTable()
    table.set("gemm", (128, 128, 128), TunedEntry(
        config={"bm": 128, "bn": 128, "bk": 128}, seconds=1.0,
        default_seconds=1.0, timed=1, pruned=0))
    save_tuning_table(table, fp)
    assert load_default_tuning_table() is not None
    monkeypatch.setenv("REPRO_NO_TUNING", "1")
    assert load_default_tuning_table() is None
    # And dispatch-time lookup goes dark too, even with a table pinned.
    backend = PallasBackend(reps=1)
    backend.set_tuning(table)
    assert backend._config_lookup("gemm", (128, 128, 128)) is None


def test_corrupt_table_degrades_to_none(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PROFILE_DIR", str(tmp_path))
    from repro.core.profile_store import current_fingerprint
    fp = current_fingerprint(backend="pallas", dtype="float32")
    tuning_path(fp).parent.mkdir(parents=True, exist_ok=True)
    tuning_path(fp).write_text("{not json")
    assert load_default_tuning_table() is None


# ------------------------------------------------- dispatch integration ---

def test_pallas_backend_auto_loads_saved_table(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PROFILE_DIR", str(tmp_path))
    from repro.core.profile_store import current_fingerprint
    fp = current_fingerprint(backend="pallas", dtype="float32")
    table = TuningTable()
    table.set("gemm", (256, 256, 256), TunedEntry(
        config={"bm": 256, "bn": 256, "bk": 128}, seconds=1.0,
        default_seconds=1.0, timed=1, pruned=0))
    save_tuning_table(table, fp)
    backend = PallasBackend(reps=1)
    loaded = backend.tuning_table()
    assert loaded is not None and len(loaded) == 1
    assert backend._config_lookup("gemm", (256, 256, 256)) == {
        "bm": 256, "bn": 256, "bk": 128}
    # The ops vocabulary sanitizes and applies the same config.
    assert backend.ops()._cfg("gemm", (256, 256, 256)) == {
        "bm": 256, "bn": 256, "bk": 128}


def test_pallas_ops_drops_unknown_config_keys():
    ops = PallasOps(lambda kind, dims: {"bm": 256, "evil": 7, "bq": 1})
    assert ops._cfg("gemm", (256, 256, 256)) == {"bm": 256}
    assert ops._cfg("syrk", (256, 256)) == {"bm": 256}


def test_tuning_override_wins_over_table_and_is_scoped():
    backend = PallasBackend(reps=1, tuning=None)
    dims = (256, 256, 256)
    assert backend._config_lookup("gemm", dims) is None
    with backend.tuning_override({("gemm", dims): {"bm": 256}}):
        assert backend._config_lookup("gemm", dims) == {"bm": 256}
        assert backend._config_lookup("gemm", (128, 128, 128)) is None
    assert backend._config_lookup("gemm", dims) is None


def test_tuned_config_changes_execution_and_stays_correct():
    # End-to-end: a tuned table entry reaches the kernel (observed via the
    # config lookup) and the tuned result still matches the oracle.
    table = TuningTable()
    table.set("gemm", (130, 150, 70), TunedEntry(
        config={"bm": 256, "bn": 256, "bk": 128}, seconds=1.0,
        default_seconds=1.0, timed=1, pruned=0))
    backend = PallasBackend(reps=1, tuning=table)
    from repro.core.backends.base import synthetic_algorithm
    from repro.core.flops import KernelCall
    alg = synthetic_algorithm(KernelCall("gemm", (130, 150, 70)))
    operands = backend.make_operands(alg)
    out = backend.execute(alg, operands)
    a, b = np.asarray(operands[0]), np.asarray(operands[1])
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-4, atol=1e-3)


# ------------------------------------------------------- the autotuner ---

def test_autotune_request_picks_measured_winner_and_counts():
    dims = (256, 256, 256)
    fake_times = {}

    class SpyBackend(PallasBackend):
        def make_operands(self, alg):
            return {}

        def time_algorithm(self, alg, operands=None, reps=None):
            cfg = self._config_lookup("gemm", dims) or {}
            key = tuple(sorted(cfg.items()))
            # Make one non-default config the clear winner.
            t = 1.0
            if cfg.get("bm") == 256 and not cfg.get("pipeline"):
                t = 0.25
            fake_times[key] = t
            return t

    entry = autotune_request(SpyBackend(reps=1), "gemm", dims,
                             profile=RooflineProfile())
    assert entry.config["bm"] == 256
    assert entry.seconds == pytest.approx(0.25)
    assert entry.default_seconds == pytest.approx(1.0)
    assert entry.timed == len(fake_times)
    assert entry.seconds <= entry.default_seconds


def test_autotune_probes_gemm_pipeline_on_winner_tile():
    dims = (256, 256, 256)
    seen_pipeline = []

    class SpyBackend(PallasBackend):
        def make_operands(self, alg):
            return {}

        def time_algorithm(self, alg, operands=None, reps=None):
            cfg = self._config_lookup("gemm", dims) or {}
            if cfg.get("pipeline"):
                seen_pipeline.append(dict(cfg))
                return 0.01   # the pipelined probe wins
            return 1.0

    entry = autotune_request(SpyBackend(reps=1), "gemm", dims,
                             profile=RooflineProfile())
    assert len(seen_pipeline) == 1
    assert entry.config["pipeline"] == 1


def test_default_tune_requests_dedup_and_fused_diagonal():
    from repro.core.calibrate import grid_calls
    calls = grid_calls((64, 128))
    requests = default_tune_requests(calls, fused_dims=(64, 128))
    kinds = {k for k, _ in requests}
    assert kinds == {"gemm", "syrk", "symm", "chain_gemm", "gemm_syrk"}
    assert ("tri2full", (64,)) not in requests
    assert ("chain_gemm", (64, 64, 64, 64)) in requests
    assert ("gemm_syrk", (128, 128, 128)) in requests
    assert len(requests) == len(set(requests))


def test_autotune_real_backend_tiny_request():
    # One real (interpret-mode) tuning request end to end: winner config
    # is timed, measured no slower than the measured default, and valid.
    backend = PallasBackend(reps=1, tuning=None)
    entry = autotune_request(backend, "gemm", (64, 64, 64), budget=2)
    assert entry.seconds > 0
    assert entry.seconds <= entry.default_seconds
    assert set(entry.config) <= {"bm", "bn", "bk", "pipeline"}


def test_calibrate_tune_cli_persists_and_backend_autoloads(tmp_path,
                                                          monkeypatch):
    monkeypatch.setenv("REPRO_PROFILE_DIR", str(tmp_path))
    from repro.core.calibrate import tune
    res = tune(backend="pallas", grid="tiny", reps=1, budget=1)
    assert res.path is not None and res.path.is_file()
    assert res.n_requests == len(res.table.entries)
    backend = PallasBackend(reps=1)
    loaded = backend.tuning_table()
    assert loaded is not None
    assert len(loaded) == res.n_requests
    assert loaded.config("gemm", (64, 64, 64)) is not None


def test_tune_rejects_untunable_backend():
    from repro.core.calibrate import tune
    with pytest.raises(ValueError, match="tunable"):
        tune(backend="jax", grid="tiny")


# ------------------------------------------------ shape-chosen tiles ---

NS_WIDTHS_TILES = [
    ("syrk", (3072, 3072), {"bm": 512, "bk": 512}),
    ("syrk", (3072, 8192), {"bm": 512, "bk": 512}),
    ("syrk", (4096, 4096), {"bm": 512, "bk": 512}),
    ("syrk", (512, 4096), {"bm": 512, "bk": 512}),
    ("syrk", (4096, 11008), {"bm": 512, "bk": 256}),
    ("symm", (4096, 11008), {"bm": 512, "bn": 256}),
    ("symm", (3072, 8192), {"bm": 512, "bn": 512}),
    ("symm", (512, 4096), {"bm": 256, "bn": 512}),    # two row blocks
    ("gemm", (1024, 768, 640), {"bm": 512, "bn": 256, "bk": 128}),
]


@pytest.mark.parametrize("kind,dims,expect", NS_WIDTHS_TILES,
                         ids=lambda v: str(v))
def test_shape_config_at_the_newton_schulz_widths(kind, dims, expect):
    from repro.core.tuning import shape_config
    for dtype_bytes in (2, 4):
        assert shape_config(kind, dims, dtype_bytes=dtype_bytes) == expect
    backend = PallasBackend(reps=1)
    assert backend.tuning_table() is None
    assert backend.ops()._cfg(kind, dims) == expect


@pytest.mark.parametrize("kind", ["gemm", "syrk", "symm"])
def test_shape_config_pads_no_further_than_128_and_fits_vmem(kind):
    """Every dim from 1 to 1300, on each axis: the padded work is the
    128-edge default's, and the tiles fit the kernel VMEM budget."""
    from repro.core.perfmodel import hardware_spec
    from repro.core.tuning import SHAPED_AXES, shape_config
    budget = hardware_spec().vmem_bytes
    arity = len(SHAPED_AXES[kind])
    wide = 0
    for d in range(1, 1301):
        for dims in {(d,) * arity, (d,) + (4096,) * (arity - 1),
                     (4096,) * (arity - 1) + (d,)}:
            for dtype_bytes in (2, 4):
                cfg = shape_config(kind, dims, dtype_bytes=dtype_bytes)
                if cfg is None:
                    continue
                wide += 1
                assert max(cfg.values()) > 128
                assert set(cfg) <= set(ALLOWED_KEYS[kind])
                assert padded_dims(kind, dims, cfg) == padded_dims(
                    kind, dims, {}), (dims, cfg)
                if kind == "symm" and cfg["bm"] > 128:
                    assert padded_dims(kind, dims, {})[0] >= 2 * cfg["bm"]
                assert kernel_vmem_bytes(kind, dims, cfg,
                                         dtype_bytes=dtype_bytes) <= budget
    assert wide


def test_shape_config_halves_the_widest_edge_to_fit_vmem(monkeypatch):
    from repro.core import tuning
    dims = (3072, 8192)
    # Fits 512×256 symm tiles in bf16 (3.1 MB) but not 512×512 (4.7 MB).
    small = HardwareSpec(name="test", peak_flops=1e12, hbm_bw=1e11,
                         link_bw=1e9, vmem_bytes=3_500_000)
    assert kernel_vmem_bytes("symm", dims, {"bm": 512, "bn": 512},
                             dtype_bytes=2) > small.vmem_bytes
    monkeypatch.setattr(tuning, "hardware_spec", lambda: small)
    tuning.shape_config.cache_clear()
    try:
        cfg = tuning.shape_config("symm", dims, dtype_bytes=2)
        assert cfg == {"bm": 256, "bn": 512} or cfg == {"bm": 512,
                                                        "bn": 256}
        assert kernel_vmem_bytes("symm", dims, cfg,
                                 dtype_bytes=2) <= small.vmem_bytes
    finally:
        tuning.shape_config.cache_clear()


#: The symm kernel's (m, n) in ``aatb.ns_widths`` and in the paper box
#: (chipbench/traffic): S is d0 × d0 and B d0 × d2.
NS_WIDTHS_SYMM = [(3072, 3072), (3072, 8192), (4096, 4096), (512, 4096),
                  (4096, 11008)]
PAPER_BOX_SYMM = [(215, 1178), (239, 1038), (145, 828), (715, 439),
                  (482, 1193), (995, 151), (349, 1000), (591, 795),
                  (855, 833), (1181, 1023), (120, 987), (1085, 181),
                  (531, 684), (384, 297), (497, 461), (549, 271)]


def test_symm_vmem_bytes_counts_the_panel_accumulator_and_output():
    """Each column the panel adds costs a bm-row column of the fp32
    accumulator (4 B an element) and of the double-buffered B and output
    panels (2 × 2 B each in bf16)."""
    from repro.kernels import ops
    from repro.kernels.symm import symm_vmem_bytes
    dims, cfg = (4096, 11008), {"bm": 512, "bn": 256}
    panel = ops.symm_panel_cols(11008, "bfloat16", 512, 256)
    assert panel > 256
    need = kernel_vmem_bytes("symm", dims, cfg, dtype_bytes=2)
    assert need == symm_vmem_bytes(512, panel, dtype_bytes=2)
    assert need - symm_vmem_bytes(512, 256, dtype_bytes=2) == \
        512 * (panel - 256) * (4 + 2 * 2 + 2 * 2)


@pytest.mark.parametrize("dims", NS_WIDTHS_SYMM + PAPER_BOX_SYMM, ids=str)
def test_symm_panel_fits_vmem_at_the_cell_shapes(dims):
    """At the tiles each cell runs, in bfloat16 and float32: the panel is
    whole ``bn`` blocks, fits this device's kernel VMEM budget, and
    computes under 3 % of n beyond the padded B."""
    import jax.numpy as jnp

    from repro.core.perfmodel import hardware_spec
    from repro.core.tuning import shape_config
    from repro.kernels import ops
    from repro.kernels.symm import symm_vmem_bytes
    budget = hardware_spec().vmem_bytes
    for dtype in (jnp.dtype("bfloat16"), jnp.dtype("float32")):
        cfg = dict(DEFAULT_CONFIGS["symm"], **(shape_config(
            "symm", dims, dtype_bytes=dtype.itemsize) or {}))
        _, n = padded_dims("symm", dims, cfg)
        panel = ops.symm_panel_cols(dims[1], dtype, **cfg)
        assert panel % cfg["bn"] == 0 and panel >= cfg["bn"]
        assert symm_vmem_bytes(cfg["bm"], panel,
                               dtype_bytes=dtype.itemsize) <= budget
        assert kernel_vmem_bytes("symm", dims, cfg,
                                 dtype_bytes=dtype.itemsize) <= budget
        assert -(-n // panel) * panel - n < 0.03 * n


def test_symm_panel_cuts_the_s_fetches_at_yi_9b_mlp_width():
    """(4096, 11008) at 512 × 256 in bf16: n/bn = 43 is prime, so no
    panel of whole blocks divides n. One S tile a grid step: 2752 fetches
    at one 256-column block a step, ⌈11008/panel⌉·8·8 with the panel."""
    from repro.kernels.symm import symm_panel
    panel = symm_panel(11008, 512, 256, dtype_bytes=2, budget=16 << 20)
    mb = 4096 // 512
    fetches = -(-11008 // panel) * mb * mb
    assert 11008 // 256 * mb * mb == 2752
    assert panel % 256 == 0 and panel >= 1024
    assert fetches <= 704
    assert -(-11008 // panel) * panel - 11008 < 0.03 * 11008
    # Past the VMEM budget the panel is one block again.
    assert symm_panel(11008, 512, 256, dtype_bytes=2,
                      budget=1 << 20) == 256


def test_shape_config_is_memoised_and_read_only():
    from repro.core.tuning import shape_config
    cfg = shape_config("gemm", (2048, 2048, 2048), dtype_bytes=2)
    assert cfg is shape_config("gemm", (2048, 2048, 2048), dtype_bytes=2)
    with pytest.raises(TypeError):
        cfg["bm"] = 128


@pytest.mark.parametrize("kind,dims", [
    ("gemm", (100, 1100, 384)),       # every axis rounds to an odd 128
    ("syrk", (384, 640)),
    ("symm", (239, 1038)),            # 256 would leave S one block
    ("chain_gemm", (1024, 1024, 1024, 1024)),
    ("gemm_syrk", (1024, 1024, 1024)),
    ("tri2full", (1024,)),
])
def test_shape_config_keeps_128_where_wider_pads_or_is_not_shaped(kind,
                                                                   dims):
    from repro.core.tuning import shape_config
    assert shape_config(kind, dims, dtype_bytes=2) is None
    assert PallasBackend(reps=1).ops()._cfg(kind, dims) == {}


@pytest.mark.parametrize("pin", ["tuning_none", "set_tuning_none",
                                 "kill_switch", "override"])
def test_pinned_lookups_keep_the_128_defaults(pin, monkeypatch):
    """``tuning=None``, ``set_tuning(None)``, ``REPRO_NO_TUNING=1`` and the
    autotuner's override give what they gave before the shape rule."""
    dims = (2048, 2048, 2048)
    backend = PallasBackend(reps=1, tuning=None if pin == "tuning_none"
                            else "auto")
    if pin == "set_tuning_none":
        backend.set_tuning(None)
    if pin == "kill_switch":
        monkeypatch.setenv("REPRO_NO_TUNING", "1")
    if pin == "override":
        with backend.tuning_override({("gemm", dims): {"bm": 256}}):
            assert backend._config_lookup("gemm", dims) == {"bm": 256}
            assert backend._config_lookup("gemm", (4096,) * 3) is None
        return
    assert backend._config_lookup("gemm", dims) is None
    assert backend.ops()._cfg("syrk", (2048, 2048)) == {}


@pytest.mark.parametrize("dims,expect", [
    ((2048, 2048, 2048), {"bm": 256, "bn": 128, "bk": 256}),  # exact
    ((1900, 2100, 2000), {"bm": 256, "bn": 128, "bk": 256}),  # nearest
])
def test_a_table_entry_wins_over_the_shape_rule(dims, expect, tmp_path,
                                                monkeypatch):
    monkeypatch.setenv("REPRO_PROFILE_DIR", str(tmp_path))
    from repro.core.profile_store import current_fingerprint
    table = TuningTable()
    table.set("gemm", (2048, 2048, 2048), TunedEntry(
        config=dict(expect), seconds=1.0, default_seconds=1.0, timed=1,
        pruned=0))
    save_tuning_table(table, current_fingerprint(backend="pallas",
                                                 dtype="float32"))
    backend = PallasBackend(reps=1)
    assert backend._config_lookup("gemm", dims) == expect
    # A kind the table has no entry of takes the shape rule.
    assert backend._config_lookup("syrk", (2048, 2048)) == {"bm": 512,
                                                            "bk": 512}
