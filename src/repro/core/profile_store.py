"""Persistent kernel-profile storage — calibrate once per machine.

Peise & Bientinesi (arXiv:1209.2364) make the case that kernel performance
models must be *measured on the target hardware*; this module makes those
measurements durable. A :class:`~repro.core.perfmodel.TableProfile` is
serialized to versioned JSON together with a :class:`HardwareFingerprint`
(backend, device kind, dtype) so a profile calibrated on one machine is
never silently applied to another.

Layout on disk (one file per fingerprint)::

    <cache dir>/profile-<backend>-<device>-<dtype>.json

where ``<cache dir>`` is, in order of precedence:

1. the explicit ``path``/``directory`` argument,
2. ``$REPRO_PROFILE_DIR``,
3. ``$XDG_CACHE_HOME/repro/profiles`` or ``~/.cache/repro/profiles``.

Set ``REPRO_NO_PROFILE_CACHE=1`` to make :func:`load_default_profile`
return ``None`` unconditionally (used by tests and cold-start debugging).
"""

from __future__ import annotations

import dataclasses
import json
import os
import platform
import re
from pathlib import Path
from typing import Optional, Tuple

from .perfmodel import TableProfile

SCHEMA_VERSION = 1

_ENV_DIR = "REPRO_PROFILE_DIR"
_ENV_DISABLE = "REPRO_NO_PROFILE_CACHE"


class ProfileStoreError(RuntimeError):
    """Base class for profile persistence failures."""


class FingerprintMismatchError(ProfileStoreError):
    """A stored profile was calibrated on different hardware/backend/dtype."""


class SchemaVersionError(ProfileStoreError):
    """A stored profile uses a schema this build cannot read."""


@dataclasses.dataclass(frozen=True)
class HardwareFingerprint:
    """What a calibration is valid for: backend × device kind × dtype."""

    backend: str   # "blas" | "jax"
    device: str    # e.g. "x86_64", "TPU v5e", "cpu"
    dtype: str     # e.g. "float64", "float32", "bfloat16"

    def slug(self) -> str:
        """Filesystem-safe identifier used in the cache filename."""
        raw = f"{self.backend}-{self.device}-{self.dtype}"
        return re.sub(r"[^A-Za-z0-9._-]+", "_", raw).lower()

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "HardwareFingerprint":
        return cls(backend=str(d["backend"]), device=str(d["device"]),
                   dtype=str(d["dtype"]))


def current_fingerprint(backend: str = "blas",
                        dtype: str = "float64") -> HardwareFingerprint:
    """Fingerprint of *this* process's execution target.

    For CPU backends (blas/numpy) the device is the host ISA (profiles
    transfer across same-ISA hosts only approximately, but that is the
    right granularity for a cache key). For device-sharded backends
    (jax/pallas — consulted via the execution-backend registry) it is the
    first JAX device's kind.
    """
    try:
        from .backends import backend_shard_mode
        on_device = backend_shard_mode(backend) == "device"
    except KeyError:  # unregistered label (tests, foreign caches)
        on_device = backend in ("jax", "pallas")
    if on_device:
        import jax
        device = jax.devices()[0].device_kind
    else:
        device = platform.machine() or "unknown"
    return HardwareFingerprint(backend=backend, device=device, dtype=dtype)


def cache_base_dir() -> Path:
    """Root of this package's on-disk caches (``…/repro``).

    Shared by the profile cache (``<base>/profiles``) and the sweep
    engine's anomaly atlas (``<base>/atlas`` — see
    :mod:`repro.core.sweep`), so every per-machine artifact lives under
    one directory keyed by the same hardware fingerprints.
    """
    xdg = os.environ.get("XDG_CACHE_HOME")
    base = Path(xdg) if xdg else Path.home() / ".cache"
    return base / "repro"


def cache_dir() -> Path:
    env = os.environ.get(_ENV_DIR)
    if env:
        return Path(env)
    return cache_base_dir() / "profiles"


def profile_path(fingerprint: HardwareFingerprint,
                 directory: Optional[Path] = None) -> Path:
    d = Path(directory) if directory is not None else cache_dir()
    return d / f"profile-{fingerprint.slug()}.json"


def save_profile(
    profile: TableProfile,
    fingerprint: HardwareFingerprint,
    path: Optional[Path] = None,
    directory: Optional[Path] = None,
    meta: Optional[dict] = None,
) -> Path:
    """Write ``profile`` as versioned JSON; returns the file written.

    ``path`` wins over ``directory``; with neither, the default cache dir
    is used. Parent directories are created. The write is atomic (tmp file
    + rename) so a crashed calibration never leaves a torn cache.
    """
    out = Path(path) if path is not None else profile_path(
        fingerprint, directory)
    out.parent.mkdir(parents=True, exist_ok=True)
    doc = {
        "version": SCHEMA_VERSION,
        "fingerprint": fingerprint.to_dict(),
        "peak_flops": profile.peak(),
        "entries": [
            {"kind": kind, "dims": list(dims), "seconds": t}
            for (kind, dims), t in sorted(profile.table.items())
        ],
        "meta": dict(meta or {}),
    }
    # Unique per writer: concurrent saves (benchmarks + a live planner,
    # parallel CI shards) must not interleave in a shared tmp file.
    tmp = out.with_suffix(
        f"{out.suffix}.{os.getpid()}.{os.urandom(4).hex()}.tmp")
    tmp.write_text(json.dumps(doc, indent=1, sort_keys=True))
    tmp.replace(out)
    return out


def load_profile(
    path: Path,
    expected_fingerprint: Optional[HardwareFingerprint] = None,
) -> Tuple[TableProfile, HardwareFingerprint]:
    """Read a profile; reject schema/fingerprint mismatches loudly."""
    try:
        doc = json.loads(Path(path).read_text())
    except (OSError, json.JSONDecodeError) as e:
        raise ProfileStoreError(f"unreadable profile {path}: {e}") from e
    version = doc.get("version")
    if version != SCHEMA_VERSION:
        raise SchemaVersionError(
            f"profile {path} has schema version {version!r}; "
            f"this build reads version {SCHEMA_VERSION}")
    fp = HardwareFingerprint.from_dict(doc["fingerprint"])
    if expected_fingerprint is not None and fp != expected_fingerprint:
        raise FingerprintMismatchError(
            f"profile {path} was calibrated for {fp}, "
            f"but this process targets {expected_fingerprint}")
    table = {
        (str(e["kind"]), tuple(int(d) for d in e["dims"])): float(e["seconds"])
        for e in doc["entries"]
    }
    return TableProfile(peak_flops=float(doc["peak_flops"]),
                        table=table), fp


def load_default_profile(
    backend: str = "blas",
    dtype: str = "float64",
) -> Optional[TableProfile]:
    """Auto-load the cached profile matching this machine, if any.

    Returns ``None`` (never raises) when no valid cache exists — callers
    fall back to the analytical model, so a corrupt or stale cache degrades
    to the uncalibrated behaviour instead of crashing the planner.
    """
    if os.environ.get(_ENV_DISABLE):
        return None
    fp = current_fingerprint(backend=backend, dtype=dtype)
    path = profile_path(fp)
    if not path.is_file():
        return None
    try:
        profile, _ = load_profile(path, expected_fingerprint=fp)
    except ProfileStoreError:
        return None
    return profile
