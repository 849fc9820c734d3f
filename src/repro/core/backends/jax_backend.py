"""The ``jax`` and ``pallas`` backends: jit-able executors.

``jax`` lowers every kernel kind to plain jnp (XLA picks the fusion);
``pallas`` routes gemm/syrk/symm through the hand-written Pallas TPU
kernels in :mod:`repro.kernels` (Mosaic on TPU, interpret mode on CPU —
the two must agree, which tests/test_kernels.py and the backend-parity
gate assert). Both share the generic walker, so an algorithm's step DAG
is traced once and jit treats it like any other jnp program.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import os
from typing import Callable, Dict, Mapping, Optional, Tuple

import numpy as np

from ...runtime import tracing
from ..algorithms import Algorithm
from ..arena import algorithm_structural_key
from ..tuning import ALLOWED_KEYS, shape_config
from .base import ExecutionBackend, KernelOps, num_inputs

#: Bound on each backend instance's executable memo. Far above any real
#: family's structure count (the zoo tops out at ~25 per family); evicting
#: oldest-first keeps autotuning runs (one generation per candidate) from
#: accumulating dead entries.
EXEC_MEMO_MAX = 512

def _swap(a):
    import jax.numpy as jnp
    return jnp.swapaxes(a, -1, -2)


def _mirror(t):
    import jax.numpy as jnp
    return jnp.tril(t) + _swap(jnp.tril(t, -1))


def _matmul(a, b):
    """``a @ b``; float32 operands at full precision on a TPU too."""
    import jax.numpy as jnp

    from repro.kernels._mxu import precision
    return jnp.matmul(a, b, precision=precision(a, b))


def _count_panel(s, b, n, cfg):
    """Add the columns one S tile serves in this symm call to the
    ``repro.symm.panel_cols`` counter, while a profiler session records."""
    if tracing.recording():
        import jax.numpy as jnp

        from repro.kernels import ops as kops
        tracing.count("symm.panel_cols", kops.symm_panel_cols(
            n, jnp.result_type(s, b), **cfg))


class JnpOps(KernelOps):
    """Plain-jnp kernel vocabulary (batch-dim friendly: last-two-axes ops)."""

    def transpose(self, a):
        return _swap(a)

    def gemm(self, a, b):
        return _matmul(a, b)

    def syrk(self, a):
        import jax.numpy as jnp
        return jnp.tril(_matmul(a, _swap(a)))

    def symm(self, s, b):
        return _matmul(_mirror(s), b)

    def symm_r(self, b, s):
        return _matmul(b, _mirror(s))

    def tri2full(self, t):
        return _mirror(t)


class PallasOps(JnpOps):
    """Pallas TPU kernels for the compute kinds; jnp for data movement.

    ``tri2full`` stays jnp on purpose: it is pure data movement and XLA's
    fused tril/transpose is already bandwidth-bound (see
    :func:`repro.kernels.ops.tri2full`).

    ``config_lookup(kind, dims) -> Mapping | None`` supplies tile configs
    (from a :class:`~repro.core.tuning.TuningTable`, the autotuner's
    per-candidate override, or :func:`~repro.core.tuning.shape_config`);
    ``None``/missing keys fall back to the kernels' built-in 128-edge
    defaults. Dims are read from the operands' trailing axes, so the
    lookup sees per-example shapes under vmap/jit tracing too. Unknown
    keys from a foreign table are dropped via
    :data:`~repro.core.tuning.ALLOWED_KEYS` rather than crashing the
    kernel call.

    This vocabulary also advertises the two fused patterns
    (``gemm+gemm`` → :func:`repro.kernels.ops.chain_gemm`,
    ``gemm+syrk`` → :func:`repro.kernels.ops.gemm_syrk`) unless
    ``REPRO_NO_FUSION`` is set.
    """

    def __init__(self, config_lookup: Optional[Callable[
            [str, Tuple[int, ...]], Optional[Mapping[str, int]]]] = None):
        self._lookup = config_lookup

    def _cfg(self, kind: str, dims: Tuple[int, ...]) -> dict:
        if self._lookup is None:
            return {}
        cfg = self._lookup(kind, dims)
        if not cfg:
            return {}
        allowed = ALLOWED_KEYS.get(kind, ())
        return {k: int(v) for k, v in cfg.items() if k in allowed}

    def fused_kinds(self) -> frozenset:
        if os.environ.get("REPRO_NO_FUSION"):
            return frozenset()
        return frozenset({"gemm+gemm", "gemm+syrk"})

    def gemm(self, a, b):
        from repro.kernels import ops as kops
        cfg = self._cfg("gemm", (a.shape[-2], b.shape[-1], a.shape[-1]))
        return kops.gemm(a, b, **cfg)

    def syrk(self, a):
        from repro.kernels import ops as kops
        cfg = self._cfg("syrk", (a.shape[-2], a.shape[-1]))
        return kops.syrk(a, **cfg)

    def symm(self, s, b):
        from repro.kernels import ops as kops
        cfg = self._cfg("symm", (s.shape[-2], b.shape[-1]))
        _count_panel(s, b, b.shape[-1], cfg)
        return kops.symm(s, b, **cfg)

    def symm_r(self, b, s):
        from repro.kernels import ops as kops
        # B·S with S symmetric: (S·Bᵀ)ᵀ via the side-L kernel.
        cfg = self._cfg("symm", (s.shape[-2], b.shape[-2]))
        _count_panel(s, b, b.shape[-2], cfg)
        return _swap(kops.symm(s, _swap(b), **cfg))

    def chain_gemm(self, a, b, c):
        from repro.kernels import ops as kops
        cfg = self._cfg("chain_gemm", (a.shape[-2], a.shape[-1],
                                       b.shape[-1], c.shape[-1]))
        return kops.chain_gemm(a, b, c, **cfg)

    def gemm_syrk(self, a, b):
        from repro.kernels import ops as kops
        cfg = self._cfg("gemm_syrk", (a.shape[-2], a.shape[-1],
                                      b.shape[-1]))
        return kops.gemm_syrk(a, b, **cfg)


_JNP_OPS = JnpOps()
_PALLAS_OPS = PallasOps()


class JaxBackend(ExecutionBackend):
    """Build/execute/time algorithms as jitted JAX callables.

    ``device`` pins every operand this backend synthesizes (and therefore
    the computation, which follows its inputs) to one JAX device — the
    sweep engine constructs one backend per device to shard a grid across
    all of them. ``None`` leaves placement to JAX's default.

    ``use_pallas=True`` makes this instance behave as the ``pallas``
    backend (kernel ops and fingerprint tag included) — kept so the
    legacy ``JaxRunner(use_pallas=...)`` constructor keeps working; new
    code asks the registry for ``"pallas"`` instead.
    """

    name = "jax"
    default_dtype = "float32"
    dtypes = None  # any dtype label jax can represent
    shard_mode = "device"

    def __init__(self, device=None, reps: int = 3,
                 dtype: Optional[str] = None,
                 rng: Optional[np.random.Generator] = None,
                 use_pallas: bool = False,
                 seed: Optional[int] = None):
        super().__init__(reps=reps, dtype=dtype, rng=rng, seed=seed)
        self.device = device
        self.use_pallas = bool(use_pallas)
        # Executable memo: structural key -> jitted callable. jax.jit's
        # own cache handles per-shape retraces under one wrapper; this
        # memo removes the per-point Python build + wrapper construction.
        self._exec_memo: "collections.OrderedDict[Tuple, Callable]" = (
            collections.OrderedDict())
        self.memo_hits = 0
        self.memo_misses = 0

    # -- hooks -------------------------------------------------------------
    def ops(self) -> KernelOps:
        return _PALLAS_OPS if self.use_pallas else _JNP_OPS

    def fingerprint_tags(self):
        return ("pallas" if self.use_pallas else "jax", self.dtype)

    def _asarray(self, a: np.ndarray):
        import jax
        import jax.numpy as jnp

        out = jnp.asarray(a, dtype=self.dtype)
        if out.dtype != jnp.dtype(self.dtype):
            # e.g. float64 requested with jax_enable_x64 off: JAX silently
            # downcasts, which would stamp a fingerprint the measurements
            # don't match.
            raise ValueError(
                f"jax produced dtype {out.dtype} for requested "
                f"{self.dtype!r} (for float64, enable jax_enable_x64)")
        if self.device is not None:
            out = jax.device_put(out, self.device)
        return out

    def _sync(self, out):
        import jax
        return jax.block_until_ready(out)

    def _memo_generation(self) -> Tuple:
        """Environment the traced program bakes in beyond its structure.

        The fusion kill-switch is read at trace time by
        :meth:`PallasOps.fused_kinds`; folding it into the memo key means
        flipping ``REPRO_NO_FUSION`` mid-process (tests, the
        ``--compare-backends`` A/B path) never serves a stale executable.
        """
        if self.use_pallas:
            return (bool(os.environ.get("REPRO_NO_FUSION")),)
        return ()

    def _jitted(self, alg: Algorithm) -> Callable:
        """The memoised jitted callable for ``alg``'s structure."""
        import jax

        key = (algorithm_structural_key(alg), self._memo_generation())
        fn = self._exec_memo.get(key)
        if fn is not None:
            self.memo_hits += 1
            self._exec_memo.move_to_end(key)
            return fn
        self.memo_misses += 1
        fn = jax.jit(self.build(alg))
        self._exec_memo[key] = fn
        while len(self._exec_memo) > EXEC_MEMO_MAX:
            self._exec_memo.popitem(last=False)
        return fn

    def _timed_callable(self, alg: Algorithm,
                        operands: Dict[int, object]) -> Callable[[], object]:
        """Memoised jit of the built callable; any remaining compile time
        lands in the warm-up call.

        There is no cache flush on this backend — operands live in HBM
        and the measured quantity is steady-state device time, not the
        paper's cold-cache CPU protocol.
        """
        args = self._args(alg, operands)
        fn = self._jitted(alg)
        return lambda: fn(*args)

    def _args(self, alg: Algorithm, operands: Dict[int, object]) -> list:
        n = num_inputs(alg)
        some = next(iter(operands.values()))
        # fetch only ever reads base positions; fill the rest with any array
        return [operands.get(i, some) for i in range(n)]


class PallasBackend(JaxBackend):
    """The ``pallas`` registry entry: Pallas kernels as a first-class backend.

    Interpret mode on CPU, Mosaic on TPU — same call sites either way
    (see :mod:`repro.kernels.ops`).

    Tuning: with ``tuning="auto"`` (the default) the backend lazily loads
    the :class:`~repro.core.tuning.TuningTable` cached for this machine's
    hardware fingerprint (written by ``calibrate --tune``) on first
    kernel dispatch; every gemm/syrk/symm/fused call then runs at the
    tuned tile config for its shape (nearest same-kind entry for unseen
    shapes). Where no table was found, or it has no entry of the kind, a
    gemm/syrk/symm call runs at :func:`~repro.core.tuning.shape_config`'s
    tiles, chosen from its shape and this backend's dtype; each such
    dispatch with an edge over 128 adds to the ``repro.tiles.shaped``
    counter (:mod:`repro.runtime.tracing`). Pass an explicit table, or
    ``tuning=None`` to pin the built-in 128-edge defaults;
    ``REPRO_NO_TUNING=1`` kills lookup at dispatch time regardless.
    """

    name = "pallas"
    supports_tuning = True

    def __init__(self, device=None, reps: int = 3,
                 dtype: Optional[str] = None,
                 rng: Optional[np.random.Generator] = None,
                 use_pallas: bool = True, tuning="auto",
                 seed: Optional[int] = None):
        super().__init__(device=device, reps=reps, dtype=dtype, rng=rng,
                         use_pallas=use_pallas, seed=seed)
        self._tuning = tuning          # "auto" | TuningTable | None
        self._tuning_resolved = tuning != "auto"
        #: Only the auto-loaded state falls back to shape-chosen tiles; a
        #: pinned table or ``None`` keeps the kernels' defaults.
        self._shaped = tuning == "auto"
        self._override: Optional[Callable[
            [str, Tuple[int, ...]], Optional[dict]]] = None
        #: Bumped whenever the effective config lookup changes (table
        #: swap, autotuner override enter/exit) — tile configs are baked
        #: into traced programs, so the executable memo keys on this.
        self._tuning_generation = 0

    def set_tuning(self, table) -> None:
        """Pin a :class:`~repro.core.tuning.TuningTable` (or ``None``)."""
        self._tuning = table
        self._tuning_resolved = True
        self._shaped = False
        self._tuning_generation += 1

    def tuning_table(self):
        """The resolved table (auto-load happens here), or ``None``."""
        if not self._tuning_resolved:
            from ..tuning import load_default_tuning_table
            self._tuning = load_default_tuning_table(
                backend=self.name, dtype=self.dtype)
            self._tuning_resolved = True
        return self._tuning

    @contextlib.contextmanager
    def tuning_override(self, entries: Dict[Tuple[str, Tuple[int, ...]],
                                            dict]):
        """Force exact per-``(kind, dims)`` configs for the duration.

        The autotuner's measurement hook: candidate configs are applied
        through the same lookup path production dispatch uses, bypassing
        the table and the kill-switch (a tuning run must be able to
        measure while ``REPRO_NO_TUNING`` protects production traffic).
        """
        prev = self._override
        self._override = lambda kind, dims: entries.get((kind, dims))
        self._tuning_generation += 1
        try:
            yield self
        finally:
            self._override = prev
            self._tuning_generation += 1

    def _config_lookup(self, kind: str, dims: Tuple[int, ...]
                       ) -> Optional[Mapping[str, int]]:
        if self._override is not None:
            return self._override(kind, dims)
        if os.environ.get("REPRO_NO_TUNING"):
            return None
        table = self.tuning_table()
        if table is not None:
            cfg = table.config(kind, dims)
            if cfg is not None:
                return cfg
        if not self._shaped:
            return None
        shaped = shape_config(kind, dims, self._dtype_bytes)
        if shaped is not None:
            tracing.count("tiles.shaped")
        return shaped

    @functools.cached_property
    def _dtype_bytes(self) -> int:
        import jax.numpy as jnp
        return jnp.dtype(self.dtype).itemsize

    def _memo_generation(self) -> Tuple:
        """Fusion + tuning state a traced Pallas program bakes in."""
        return (bool(os.environ.get("REPRO_NO_FUSION")),
                bool(os.environ.get("REPRO_NO_TUNING")),
                self._tuning_generation)

    def ops(self) -> KernelOps:
        return PallasOps(self._config_lookup)
