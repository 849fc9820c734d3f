"""Tile/pipeline autotuning: search space, roofline pruning, persistence.

The paper closes by conjecturing that FLOP counts must be combined with
kernel performance models to pick optimal algorithms — but a performance
model is only as honest as the kernels it measures. Our Pallas backend
used to run every kernel at a hard-coded 128³ tile, so its profiles (and
its anomaly map) measured *our defaults*, not the hardware. Peise &
Bientinesi (arXiv 1209.2364) show kernel performance varies sharply with
blocking and must be measured, not assumed; Sankaran & Bientinesi
(arXiv 2209.03258) show a small measurement budget spent on the cheapest
candidates ranks reliably. This module is the search-space half of that
tuner (the measurement loop lives in :mod:`repro.kernels.autotune`):

* :func:`candidate_configs` — per-kernel-kind tile candidates
  (``bm``/``bn``/``bk``/``bl`` block edges over :data:`BLOCK_CHOICES`).
* :func:`prune_candidates` — the
  :class:`~repro.core.perfmodel.RooflineProfile`-driven pre-filter:
  candidates whose VMEM footprint exceeds the hardware budget
  (:func:`kernel_vmem_bytes`, ``chain_gemm_vmem_bytes``-style estimates)
  or whose roofline-modeled time — the arithmetic-intensity bound: padded
  MXU work vs. per-tiling HBM traffic — is more than ``slack×`` the best
  candidate's are rejected *before any timing is spent on them*.
* :class:`TuningTable` — the persisted winners, keyed ``(kind, dims)``
  with nearest-config fallback in log-dim space for unseen shapes, saved
  as versioned JSON under the same
  :class:`~repro.core.profile_store.HardwareFingerprint` scheme (and
  cache directory) as calibration profiles:
  ``<cache dir>/tuning-<backend>-<device>-<dtype>.json``.

``calibrate --tune --backend pallas`` writes the table;
:class:`~repro.core.backends.jax_backend.PallasBackend` auto-loads it.
Where the auto-loaded table has no entry of a kind, or there is no
table, the backend takes :func:`shape_config`'s tiles, chosen from the
shape alone. Set ``REPRO_NO_TUNING=1`` to kill both (the kernels fall
back to their 128³ defaults).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import types
from pathlib import Path
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

from .perfmodel import RooflineProfile, hardware_spec
from .profile_store import (
    HardwareFingerprint,
    ProfileStoreError,
    FingerprintMismatchError,
    SchemaVersionError,
    cache_dir,
    current_fingerprint,
)

TUNING_SCHEMA_VERSION = 1

#: Env kill-switch: disables both TuningTable auto-load and tuned-config
#: lookup on the Pallas backend (kernels run at their built-in defaults).
ENV_NO_TUNING = "REPRO_NO_TUNING"

#: Block-edge candidates per tile axis. 128 is the MXU edge (the old
#: hard-coded default); larger powers of two trade VMEM residency for
#: fewer grid steps and less operand re-streaming.
BLOCK_CHOICES: Tuple[int, ...] = (128, 256, 512)

#: Per-kind default configs — the hard-coded tiles the kernels ship with.
#: The autotuner always times the default alongside the pruned survivors,
#: so a persisted winner is never slower than the default *as measured*.
DEFAULT_CONFIGS: Dict[str, Dict[str, int]] = {
    "gemm": {"bm": 128, "bn": 128, "bk": 128, "pipeline": 0},
    "syrk": {"bm": 128, "bk": 128},
    "symm": {"bm": 128, "bn": 128},
    "chain_gemm": {"bm": 128, "bn": 128, "bk": 128, "bl": 128},
    "gemm_syrk": {"bm": 128, "bk": 128},
}

#: Config keys each kernel wrapper accepts — lookups are sanitized
#: through this so a foreign/hand-edited table entry can never crash a
#: kernel call with an unexpected keyword.
ALLOWED_KEYS: Dict[str, Tuple[str, ...]] = {
    kind: tuple(cfg) for kind, cfg in DEFAULT_CONFIGS.items()
}

#: Kinds the tuner searches. ``tri2full`` is pure data movement with no
#: tile parameters — nothing to tune.
TUNABLE_KINDS: Tuple[str, ...] = tuple(DEFAULT_CONFIGS)


def _ceil_to(x: int, q: int) -> int:
    return ((x + q - 1) // q) * q


def config_key(config: Dict[str, int]) -> Tuple[Tuple[str, int], ...]:
    """Hashable, order-independent identity of a candidate config."""
    return tuple(sorted(config.items()))


def candidate_configs(kind: str,
                      dims: Sequence[int]) -> List[Dict[str, int]]:
    """The tile search space for one ``(kind, dims)`` tuning request.

    Pure cross product of :data:`BLOCK_CHOICES` over the kind's tile
    axes; the roofline pre-filter (:func:`prune_candidates`) is what
    keeps this affordable. The gemm ``pipeline`` knob (Mosaic
    ``dimension_semantics`` grid annotation) is *not* enumerated here —
    it does not change the roofline model, so the measurement loop
    probes it on the winning tile only (see
    :func:`repro.kernels.autotune.autotune_request`).
    """
    if kind not in TUNABLE_KINDS:
        raise ValueError(
            f"kernel kind {kind!r} is not tunable; expected one of "
            f"{TUNABLE_KINDS}")
    c = BLOCK_CHOICES
    if kind == "gemm":
        return [{"bm": bm, "bn": bn, "bk": bk}
                for bm in c for bn in c for bk in c]
    if kind == "syrk":
        return [{"bm": bm, "bk": bk} for bm in c for bk in c]
    if kind == "symm":
        return [{"bm": bm, "bn": bn} for bm in c for bn in c]
    if kind == "chain_gemm":
        return [{"bm": bm, "bn": bn, "bk": bk, "bl": bl}
                for bm in c for bn in c for bk in c for bl in c]
    # gemm_syrk: the intermediate row-block and B stay fully VMEM-resident,
    # so only the output block edge and the K slab are free.
    return [{"bm": bm, "bk": bk} for bm in c for bk in c]


def padded_dims(kind: str, dims: Sequence[int],
                config: Dict[str, int]) -> Tuple[int, ...]:
    """Problem dims after the ``ops.*`` wrapper pads to block multiples.

    This is the work actually scheduled — the quantization the perf
    model charges for; a 129-row GEMM at ``bm=512`` pays for 512 rows.
    """
    d = dict(DEFAULT_CONFIGS[kind], **config)
    if kind == "gemm":
        m, n, k = dims
        return (_ceil_to(m, d["bm"]), _ceil_to(n, d["bn"]),
                _ceil_to(k, d["bk"]))
    if kind == "syrk":
        m, k = dims
        return (_ceil_to(m, d["bm"]), _ceil_to(k, d["bk"]))
    if kind == "symm":
        m, n = dims
        return (_ceil_to(m, d["bm"]), _ceil_to(n, d["bn"]))
    if kind == "chain_gemm":
        m, k, l, n = dims
        return (_ceil_to(m, d["bm"]), _ceil_to(k, d["bk"]),
                _ceil_to(l, d["bl"]), _ceil_to(n, d["bn"]))
    if kind == "gemm_syrk":
        m, k, l = dims
        return (_ceil_to(m, d["bm"]), _ceil_to(k, d["bk"]),
                _ceil_to(l, 128))
    raise ValueError(f"kernel kind {kind!r} is not tunable")


def kernel_vmem_bytes(kind: str, dims: Sequence[int],
                      config: Dict[str, int], *, dtype_bytes: int) -> int:
    """Estimated per-program VMEM residency of one candidate tiling.

    ``chain_gemm_vmem_bytes``-style accounting: streamed operand tiles
    are charged twice (Mosaic double-buffers the pipeline), fp32
    accumulator scratch is charged at 4 bytes regardless of the operand
    dtype. The chain kinds and symm delegate to the estimators that live
    next to their kernels so the pre-filter and the wrapper can never
    disagree; a symm is charged at the column panel its wrapper chooses
    for this device's VMEM budget.
    """
    d = dict(DEFAULT_CONFIGS[kind], **config)
    bm = d.get("bm", 128)
    if kind == "gemm":
        bn, bk = d["bn"], d["bk"]
        return 2 * (bm * bk + bk * bn + bm * bn) * dtype_bytes \
            + bm * bn * 4
    if kind == "syrk":
        bk = d["bk"]
        return 2 * (2 * bm * bk + bm * bm) * dtype_bytes + bm * bm * 4
    if kind == "symm":
        from repro.kernels.symm import symm_panel, symm_vmem_bytes
        _, np_ = padded_dims(kind, dims, d)
        panel = symm_panel(np_, bm, d["bn"], dtype_bytes=dtype_bytes,
                           budget=hardware_spec().vmem_bytes)
        return symm_vmem_bytes(bm, panel, dtype_bytes=dtype_bytes)
    if kind == "chain_gemm":
        from repro.kernels.chain_gemm import chain_gemm_vmem_bytes
        mp, kp, lp, np_ = padded_dims(kind, dims, d)
        return chain_gemm_vmem_bytes(mp, kp, lp, np_, bm=bm, bn=d["bn"],
                                     bk=d["bk"], dtype_bytes=dtype_bytes)
    if kind == "gemm_syrk":
        from repro.kernels.chain_gemm import gemm_syrk_vmem_bytes
        mp, kp, lp = padded_dims(kind, dims, d)
        return gemm_syrk_vmem_bytes(mp, kp, lp, bm=bm, bk=d["bk"],
                                    dtype_bytes=dtype_bytes)
    raise ValueError(f"kernel kind {kind!r} is not tunable")


#: Tile axis → index of the dim it tiles, for the kinds
#: :func:`shape_config` chooses tiles for. ``symm_r`` dispatches as
#: ``symm``. The fused kinds stay at 128: their VMEM estimates do not match
#: the compiler at larger tiles.
SHAPED_AXES: Dict[str, Dict[str, int]] = {
    "gemm": {"bm": 0, "bn": 1, "bk": 2},
    "syrk": {"bm": 0, "bk": 1},
    "symm": {"bm": 0, "bn": 1},
}


@functools.lru_cache(maxsize=65536)
def shape_config(kind: str, dims: Tuple[int, ...],
                 dtype_bytes: int) -> Optional[Mapping[str, int]]:
    """Tiles chosen from the shape alone, for a lookup with no tuned entry.

    Each tile axis takes the largest edge in :data:`BLOCK_CHOICES` that
    pads its dim no further than the 128 rounding does, so the kernel
    does the work it does at the 128-edge defaults in fewer grid steps;
    a symm's row edge leaves S two row blocks at least.
    While :func:`kernel_vmem_bytes` exceeds this device's kernel VMEM
    budget the largest edge is halved (the 128 config always fits).

    ``None`` where every edge stays 128, and for the kinds outside
    :data:`SHAPED_AXES`: the kernels' defaults apply. Memoised, so an
    eager dispatch pays one cache hit; the mapping returned is shared and
    read-only, and holds only keys of :data:`ALLOWED_KEYS`.
    """
    axes = SHAPED_AXES.get(kind)
    if axes is None:
        return None
    config = {}
    for name, i in axes.items():
        padded = _ceil_to(dims[i], 128)
        edges = [e for e in BLOCK_CHOICES if _ceil_to(dims[i], e) == padded]
        if kind == "symm" and name == "bm":
            # The TPU compiler refuses a bfloat16 symm whose S is a single
            # block wider than 128 (a RET_CHECK in its MXU transpose pass),
            # so S keeps two row blocks at least.
            edges = [e for e in edges if e == 128 or e < padded]
        config[name] = max(edges)
    budget = hardware_spec().vmem_bytes
    while max(config.values()) > 128 and kernel_vmem_bytes(
            kind, dims, config, dtype_bytes=dtype_bytes) > budget:
        widest = max(config, key=config.get)
        config[widest] //= 2
    if max(config.values()) == 128:
        return None
    return types.MappingProxyType(config)


def padded_flops(kind: str, dims: Sequence[int],
                 config: Dict[str, int]) -> int:
    """MXU work actually scheduled under one tiling (block-quantized)."""
    if kind == "gemm":
        mp, np_, kp = padded_dims(kind, dims, config)
        return 2 * mp * np_ * kp
    if kind == "syrk":
        d = dict(DEFAULT_CONFIGS[kind], **config)
        mp, kp = padded_dims(kind, dims, config)
        mt = mp // d["bm"]
        return (mt * (mt + 1) // 2) * 2 * d["bm"] * d["bm"] * kp
    if kind == "symm":
        mp, np_ = padded_dims(kind, dims, config)
        return 2 * mp * mp * np_
    if kind == "chain_gemm":
        mp, kp, lp, np_ = padded_dims(kind, dims, config)
        return 2 * mp * kp * lp + 2 * mp * np_ * lp
    if kind == "gemm_syrk":
        d = dict(DEFAULT_CONFIGS[kind], **config)
        mp, kp, lp = padded_dims(kind, dims, config)
        mt = mp // d["bm"]
        t_blocks = mt * (mt + 1) // 2
        # Two intermediate row-blocks recomputed per output block + the
        # outer product itself — the fusion's recompute-vs-HBM trade.
        return t_blocks * (4 * d["bm"] * kp * lp + 2 * d["bm"] * d["bm"] * lp)
    raise ValueError(f"kernel kind {kind!r} is not tunable")


def traffic_elems(kind: str, dims: Sequence[int],
                  config: Dict[str, int]) -> int:
    """HBM traffic (elements) of one tiling: operand re-streaming + output.

    This is where tile size earns its keep: a GEMM A-panel is re-read
    once per N-block, so doubling ``bn`` halves A traffic — the
    arithmetic-intensity lever the pre-filter ranks candidates by.
    """
    d = dict(DEFAULT_CONFIGS[kind], **config)
    bm = d.get("bm", 128)
    if kind == "gemm":
        mp, np_, kp = padded_dims(kind, dims, d)
        return mp * kp * (np_ // d["bn"]) + kp * np_ * (mp // bm) + mp * np_
    if kind == "syrk":
        mp, kp = padded_dims(kind, dims, d)
        mt = mp // bm
        return (mt * (mt + 1) // 2) * 2 * bm * kp + mp * mp
    if kind == "symm":
        mp, np_ = padded_dims(kind, dims, d)
        mt, nt = mp // bm, np_ // d["bn"]
        return mp * mp * nt + mp * np_ * mt + mp * np_
    if kind == "chain_gemm":
        mp, kp, lp, np_ = padded_dims(kind, dims, d)
        mt, nt = mp // bm, np_ // d["bn"]
        return mp * kp * nt + kp * lp * mt * nt + lp * np_ * mt + mp * np_
    if kind == "gemm_syrk":
        mp, kp, lp = padded_dims(kind, dims, d)
        mt = mp // bm
        t_blocks = mt * (mt + 1) // 2
        return t_blocks * (2 * bm * kp + kp * lp) + mp * mp
    raise ValueError(f"kernel kind {kind!r} is not tunable")


def modeled_time(kind: str, dims: Sequence[int], config: Dict[str, int],
                 profile: RooflineProfile, *, dtype_bytes: int) -> float:
    """Roofline-modeled seconds for one candidate tiling."""
    return profile.raw_time(padded_flops(kind, dims, config),
                            traffic_elems(kind, dims, config),
                            dtype_bytes=dtype_bytes)


def arithmetic_intensity(kind: str, dims: Sequence[int],
                         config: Dict[str, int], *,
                         dtype_bytes: int) -> float:
    """FLOPs per HBM byte under one tiling (the roofline x-axis)."""
    return padded_flops(kind, dims, config) / max(
        1, traffic_elems(kind, dims, config) * dtype_bytes)


@dataclasses.dataclass(frozen=True)
class RejectedCandidate:
    """One pruned config and why it never reached the timer."""

    config: Dict[str, int]
    reason: str    # "vmem" | "padding" | "roofline"
    detail: str


@dataclasses.dataclass
class PruneReport:
    """What the pre-filter decided for one ``(kind, dims)`` request.

    ``survivors`` are ordered cheapest-modeled-first (the Sankaran
    measurement order) and always contain the kind's default config;
    ``modeled`` aligns with ``survivors``.
    """

    kind: str
    dims: Tuple[int, ...]
    survivors: List[Dict[str, int]]
    modeled: List[float]
    rejected: List[RejectedCandidate]


def prune_candidates(
    kind: str,
    dims: Sequence[int],
    candidates: Optional[Iterable[Dict[str, int]]] = None,
    profile: Optional[RooflineProfile] = None,
    *,
    dtype_bytes: int = 4,
    slack: float = 2.0,
    max_survivors: int = 8,
) -> PruneReport:
    """The roofline pre-filter: decide which candidates deserve timing.

    Three rejection rules, applied in order and all *before* any timing:

    1. **vmem** — :func:`kernel_vmem_bytes` above the kernel VMEM budget
       (``profile.hw.vmem_bytes``; without a profile, this device's
       :func:`~repro.core.perfmodel.hardware_spec`, the number the
       ``ops.*`` wrappers guard with). Mosaic refuses such a config.
    2. **padding** — a block edge strictly larger than the dim it tiles
       (after MXU-128 rounding). The extra work is pure zero-padding; the
       same-shape 128 block dominates it.
    3. **roofline** — modeled time (:func:`modeled_time`: block-quantized
       MXU work vs. tiling-dependent HBM traffic — the arithmetic-
       intensity bound) worse than ``slack ×`` the best candidate's.

    Survivors are sorted cheapest-modeled-first and capped at
    ``max_survivors`` — the measurement budget is spent on the
    candidates the model already likes, which Sankaran & Bientinesi show
    is enough to rank reliably. The kind's default config is always
    re-appended if the cap or the roofline rule dropped it, so the
    measured winner can never lose to the default silently.
    """
    if profile is None:
        # The kernels' roofline is v5e's wherever they run (interpret mode
        # stands in for it); the VMEM budget is this device's.
        profile = RooflineProfile()
        budget = hardware_spec().vmem_bytes
    else:
        budget = profile.hw.vmem_bytes
    dims = tuple(int(d) for d in dims)
    if candidates is None:
        candidates = candidate_configs(kind, dims)
    default = dict(DEFAULT_CONFIGS[kind])
    kept: List[Tuple[float, Tuple[Tuple[str, int], ...], Dict[str, int]]] = []
    rejected: List[RejectedCandidate] = []
    for cfg in candidates:
        need = kernel_vmem_bytes(kind, dims, cfg, dtype_bytes=dtype_bytes)
        if need > budget:
            rejected.append(RejectedCandidate(
                dict(cfg), "vmem",
                f"needs {need} B > budget {budget} B"))
            continue
        waste = _padding_waste(kind, dims, cfg)
        if waste is not None:
            rejected.append(RejectedCandidate(dict(cfg), "padding", waste))
            continue
        t = modeled_time(kind, dims, cfg, profile, dtype_bytes=dtype_bytes)
        kept.append((t, config_key(cfg), dict(cfg)))
    kept.sort(key=lambda e: (e[0], e[1]))
    survivors: List[Dict[str, int]] = []
    modeled: List[float] = []
    if kept:
        best = kept[0][0]
        for t, _, cfg in kept:
            if t > slack * best and not math.isclose(t, slack * best):
                rejected.append(RejectedCandidate(
                    cfg, "roofline",
                    f"modeled {t:.3g}s > {slack:g}x best {best:.3g}s"))
            elif len(survivors) < max_survivors:
                survivors.append(cfg)
                modeled.append(t)
            else:
                rejected.append(RejectedCandidate(
                    cfg, "roofline",
                    f"budget cap: {max_survivors} cheaper candidates"))
    if not any(_same_tiles(c, default) for c in survivors):
        # The default 128-edge tiles always fit VMEM and never over-pad;
        # only the roofline cap can have dropped them. Re-admit so the
        # winner is measured against the status quo.
        survivors.append(default)
        modeled.append(modeled_time(kind, dims, default, profile,
                                    dtype_bytes=dtype_bytes))
    return PruneReport(kind=kind, dims=dims, survivors=survivors,
                       modeled=modeled, rejected=rejected)


def _same_tiles(a: Dict[str, int], b: Dict[str, int]) -> bool:
    """Tile-axis equality, ignoring non-tile knobs like ``pipeline``."""
    keys = (set(a) | set(b)) - {"pipeline"}
    return all(a.get(k, 128) == b.get(k, 128) for k in keys)


def _padding_waste(kind: str, dims: Sequence[int],
                   config: Dict[str, int]) -> Optional[str]:
    """Reason string when a block edge exceeds its (128-rounded) dim."""
    d = dict(DEFAULT_CONFIGS[kind], **config)
    axes: Dict[str, Tuple[str, int]]
    if kind == "gemm":
        m, n, k = dims
        axes = {"bm": ("m", m), "bn": ("n", n), "bk": ("k", k)}
    elif kind == "syrk":
        m, k = dims
        axes = {"bm": ("m", m), "bk": ("k", k)}
    elif kind == "symm":
        m, n = dims
        axes = {"bm": ("m", m), "bn": ("n", n)}
    elif kind == "chain_gemm":
        m, k, l, n = dims
        axes = {"bm": ("m", m), "bk": ("k", k), "bl": ("l", l),
                "bn": ("n", n)}
    else:  # gemm_syrk
        m, k, _ = dims
        axes = {"bm": ("m", m), "bk": ("k", k)}
    for block_name, (dim_name, dim) in axes.items():
        blk = d[block_name]
        if blk > 128 and blk > _ceil_to(dim, 128):
            return (f"{block_name}={blk} > padded {dim_name}="
                    f"{_ceil_to(dim, 128)}: pure zero-padding")
    return None


# ------------------------------------------------------------ the table ---


@dataclasses.dataclass
class TunedEntry:
    """The persisted outcome of tuning one ``(kind, dims)`` request."""

    config: Dict[str, int]
    seconds: float          # measured time of the winning config
    default_seconds: float  # measured time of the default tiles
    timed: int              # candidates that reached the timer
    pruned: int             # candidates the pre-filter rejected


class TuningTable:
    """Winning tile configs per ``(kind, dims)``, with nearest fallback.

    The tuning analogue of :class:`~repro.core.perfmodel.TableProfile`:
    exact hits serve the calibrated shapes, and unseen shapes borrow the
    config of the nearest same-kind entry in log-dim space (tile
    preferences vary smoothly with aspect ratio, so the neighbour's
    blocking is a far better prior than the hard-coded default).
    """

    def __init__(self, entries: Optional[Dict[Tuple[str, Tuple[int, ...]],
                                              TunedEntry]] = None,
                 meta: Optional[dict] = None):
        self.entries: Dict[Tuple[str, Tuple[int, ...]], TunedEntry] = dict(
            entries or {})
        self.meta = dict(meta or {})

    def __len__(self) -> int:
        return len(self.entries)

    def __contains__(self, key: Tuple[str, Tuple[int, ...]]) -> bool:
        return key in self.entries

    def set(self, kind: str, dims: Sequence[int],
            entry: TunedEntry) -> None:
        self.entries[(kind, tuple(int(d) for d in dims))] = entry

    def entry(self, kind: str, dims: Sequence[int]
              ) -> Optional[TunedEntry]:
        """Exact-match entry, or ``None``."""
        return self.entries.get((kind, tuple(int(d) for d in dims)))

    def config(self, kind: str, dims: Sequence[int]
               ) -> Optional[Dict[str, int]]:
        """Winning config for ``(kind, dims)`` — exact or nearest.

        Nearest = smallest squared log-dim distance among same-kind,
        same-arity entries (the :meth:`TableProfile.nearest` metric).
        Returns ``None`` when the table has no entry of this kind, so
        callers fall back to the kernel's built-in defaults.
        """
        dims = tuple(int(d) for d in dims)
        hit = self.entries.get((kind, dims))
        if hit is not None:
            return dict(hit.config)
        best: Optional[Tuple[float, Tuple[int, ...]]] = None
        for (ekind, edims), entry in self.entries.items():
            if ekind != kind or len(edims) != len(dims):
                continue
            dist = sum(
                (math.log(max(a, 2)) - math.log(max(b, 2))) ** 2
                for a, b in zip(dims, edims))
            if best is None or (dist, edims) < best:
                best = (dist, edims)
        if best is None:
            return None
        return dict(self.entries[(kind, best[1])].config)


# -------------------------------------------------------------- storage ---


def tuning_path(fingerprint: HardwareFingerprint,
                directory: Optional[Path] = None) -> Path:
    """Where this fingerprint's tuning table lives (profile cache dir)."""
    d = Path(directory) if directory is not None else cache_dir()
    return d / f"tuning-{fingerprint.slug()}.json"


def save_tuning_table(
    table: TuningTable,
    fingerprint: HardwareFingerprint,
    path: Optional[Path] = None,
    directory: Optional[Path] = None,
    meta: Optional[dict] = None,
) -> Path:
    """Write the table as versioned JSON (atomic tmp-file + rename)."""
    out = Path(path) if path is not None else tuning_path(fingerprint,
                                                          directory)
    out.parent.mkdir(parents=True, exist_ok=True)
    doc = {
        "version": TUNING_SCHEMA_VERSION,
        "fingerprint": fingerprint.to_dict(),
        "entries": [
            {"kind": kind, "dims": list(dims), "config": e.config,
             "seconds": e.seconds, "default_seconds": e.default_seconds,
             "timed": e.timed, "pruned": e.pruned}
            for (kind, dims), e in sorted(table.entries.items())
        ],
        "meta": {**table.meta, **(meta or {})},
    }
    tmp = out.with_suffix(
        f"{out.suffix}.{os.getpid()}.{os.urandom(4).hex()}.tmp")
    tmp.write_text(json.dumps(doc, indent=1, sort_keys=True))
    tmp.replace(out)
    return out


def load_tuning_table(
    path: Path,
    expected_fingerprint: Optional[HardwareFingerprint] = None,
) -> Tuple[TuningTable, HardwareFingerprint]:
    """Read a tuning table; reject schema/fingerprint mismatches loudly."""
    try:
        doc = json.loads(Path(path).read_text())
    except (OSError, json.JSONDecodeError) as e:
        raise ProfileStoreError(f"unreadable tuning table {path}: {e}") from e
    version = doc.get("version")
    if version != TUNING_SCHEMA_VERSION:
        raise SchemaVersionError(
            f"tuning table {path} has schema version {version!r}; "
            f"this build reads version {TUNING_SCHEMA_VERSION}")
    fp = HardwareFingerprint.from_dict(doc["fingerprint"])
    if expected_fingerprint is not None and fp != expected_fingerprint:
        raise FingerprintMismatchError(
            f"tuning table {path} was tuned for {fp}, "
            f"but this process targets {expected_fingerprint}")
    entries = {}
    for e in doc["entries"]:
        key = (str(e["kind"]), tuple(int(d) for d in e["dims"]))
        entries[key] = TunedEntry(
            config={str(k): int(v) for k, v in e["config"].items()},
            seconds=float(e["seconds"]),
            default_seconds=float(e.get("default_seconds", 0.0)),
            timed=int(e.get("timed", 0)),
            pruned=int(e.get("pruned", 0)))
    return TuningTable(entries=entries, meta=dict(doc.get("meta") or {})), fp


def load_default_tuning_table(
    backend: str = "pallas",
    dtype: str = "float32",
) -> Optional[TuningTable]:
    """Auto-load the cached tuning table matching this machine, if any.

    Mirrors :func:`~repro.core.profile_store.load_default_profile`:
    returns ``None`` (never raises) when tuning is killed via
    ``REPRO_NO_TUNING``, no table exists, or the cached one is
    unreadable/mismatched — the kernels then run at their defaults.
    """
    if os.environ.get(ENV_NO_TUNING):
        return None
    fp = current_fingerprint(backend=backend, dtype=dtype)
    path = tuning_path(fp)
    if not path.is_file():
        return None
    try:
        table, _ = load_tuning_table(path, expected_fingerprint=fp)
    except ProfileStoreError:
        return None
    return table
