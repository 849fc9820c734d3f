"""The LAMP planner: expression → selected algorithm → JAX callable.

This is the paper's contribution as a *runtime feature*: model code hands a
linear-algebra expression (chain, Gram product) plus concrete sizes to
:func:`plan`, and gets back a jit-able callable implementing the algorithm
chosen by the configured discriminant. Plans are memoised per
(expression-structure, sizes, discriminant, profile) so that planning cost
is paid once per shape — the common case in training where shapes are
static across steps.

Profiles resolve in three tiers (see ISSUE: calibrated-profile subsystem):

1. an explicit ``profile=`` argument wins;
2. otherwise a persisted calibration for this machine is auto-loaded from
   the profile cache (:mod:`repro.core.profile_store`) and wrapped in the
   hybrid measured-∨-analytical policy;
3. otherwise the closed-form :class:`AnalyticalTPUProfile`.

With ``record=True`` the planner additionally *refines* the live profile
online: each ``planner(chain, *arrays)`` execution is timed (blocking on
JAX async dispatch) and the observed wall time is apportioned over the
plan's kernel calls and blended into the table — so production traffic
keeps sharpening the model the calibration seeded. ``planner.save()``
persists the refined table back to the cache.

The planner is consumed by:
  * ``repro.serve.plan_cache`` — the serving layer's concurrent shape→plan
    cache (lock-free hits, coalesced misses, async refinement);
  * ``repro.models.attention`` — decode-step P·V·Wo association order is
    chosen by the planner at trace time (the ``decattn`` zoo family);
  * ``repro.core.sweep`` / the benchmarks — batch enumeration+selection.

See docs/serving.md for the request-path view of this module.
"""

from __future__ import annotations

import dataclasses
import threading
import warnings
from pathlib import Path
from typing import Callable, Dict, Optional, Tuple

from ..runtime.tracing import span
from .algorithms import Algorithm, enumerate_algorithms
from .backends import get_backend, measure_seconds
from .expr import Chain, bind_dims
from .perfmodel import AnalyticalTPUProfile, KernelProfile, TableProfile
from .profile_store import (
    current_fingerprint,
    load_default_profile,
    save_profile,
)
from .discriminants import as_hybrid, get_discriminant
from .selector import select


@dataclasses.dataclass
class Plan:
    algorithm: Algorithm
    fn: Callable            # jax callable: (*leaf_arrays) -> result
    ranked: Tuple[str, ...]  # algorithm names, best first (for logging)
    discriminant: str

    @property
    def flops(self) -> int:
        return self.algorithm.flops


def resolve_profile(
    profile: Optional[KernelProfile] = None,
    backend: str = "blas",
    dtype: str = "float64",
) -> KernelProfile:
    """Tiered profile resolution: explicit → cached calibration → analytical.

    A cached :class:`TableProfile` is wrapped into the hybrid policy so
    shapes the calibration never measured still get analytical estimates.
    """
    if profile is not None:
        return profile
    cached = load_default_profile(backend=backend, dtype=dtype)
    if cached is not None:
        return as_hybrid(cached)
    return AnalyticalTPUProfile()


class Planner:
    """Thread-safe, memoising planner with optional online refinement.

    ``record=True`` semantics (post-calibration-subsystem behaviour): every
    ``planner(chain, *arrays)`` execution is wall-timed with a block on JAX
    async dispatch, the observed seconds are apportioned over the plan's
    kernel calls in proportion to the *analytical* model's relative call
    costs (one consistent weight model — see :meth:`observe`), and each
    share is EMA-blended (``observation_blend``, default 0.25) into the
    live table profile. Refinement needs a table to write into: a pure
    analytical profile makes ``observe`` a silent no-op. ``planner.save()``
    persists the refined table under this planner's
    ``(profile_backend, profile_dtype)`` fingerprint — by default
    ``jax/float32`` when recording, so online JAX timings are never filed
    under the ``blas/float64`` calibration that Experiment 3 trusts as
    isolated BLAS benchmarks.

    Example (pure-arithmetic policy, no profile or hardware needed)::

        >>> from repro.core.expr import matrix_chain
        >>> from repro.core.planner import Planner
        >>> planner = Planner(discriminant="flops", backend="numpy")
        >>> plan = planner.plan(matrix_chain(8, 512, 8, 512))
        >>> plan.discriminant
        'flops'
        >>> plan.algorithm.name          # (8×512)·(512×8) first is cheapest
        'alg1[gemm+gemm]'
        >>> len(plan.ranked)             # 3-operand chain: 2 orders ranked
        2
        >>> planner.plan(matrix_chain(8, 512, 8, 512)) is plan  # memoised
        True
    """

    def __init__(
        self,
        discriminant: str = "perfmodel",
        profile: Optional[KernelProfile] = None,
        backend: Optional[str] = None,
        dtype_bytes: int = 2,
        record: bool = False,
        observation_blend: float = 0.25,
        profile_backend: Optional[str] = None,
        profile_dtype: Optional[str] = None,
        use_pallas: Optional[bool] = None,
    ):
        # ``backend`` is an execution-backend registry name; the planner
        # builds its callables with that backend's kernels. ``use_pallas``
        # is the pre-registry spelling, kept as a deprecation shim.
        if use_pallas is not None:
            warnings.warn(
                "Planner(use_pallas=...) is deprecated; pass "
                "backend='pallas' (or 'jax') instead",
                DeprecationWarning, stacklevel=2)
            if backend is None:
                backend = "pallas" if use_pallas else "jax"
        self.backend = backend or "jax"
        self.runner = get_backend(self.backend)
        # One (backend, dtype) key governs BOTH the cache load in
        # resolve_profile and save() below — asymmetric keys would persist
        # refinements to a file no future load ever reads. The default key
        # depends on `record`: a read-only planner consumes the BLAS
        # calibration (the CLI's default output), but a recording planner
        # produces timings via its own execution backend, and those must
        # never be filed under the blas/float64 fingerprint experiment3
        # trusts as isolated BLAS benchmarks — so the recording default is
        # the runner's own fingerprint tag (jax/float32, pallas/float32…).
        run_tag, run_dtype = self.runner.fingerprint_tags()
        if profile_backend is None:
            profile_backend = run_tag if record else "blas"
        if profile_dtype is None:
            profile_dtype = run_dtype if record else "float64"
        self.profile_backend = profile_backend
        self.profile_dtype = profile_dtype
        # Any repro.core.discriminants registry key works; resolving at
        # construction surfaces typos before the first plan() call, and
        # the policy's capability flags drive both which arguments select
        # receives and whether profile refinement invalidates memos.
        try:
            self._policy = get_discriminant(discriminant)
        except KeyError as e:
            raise ValueError(str(e)) from None
        self.discriminant = discriminant
        self.profile = resolve_profile(profile, backend=profile_backend,
                                       dtype=profile_dtype)
        self.dtype_bytes = dtype_bytes
        self.record = record
        self.observation_blend = observation_blend
        # One slot per (structure, dims, discriminant); the stored value
        # carries the profile generation it was ranked under, so online
        # refinement invalidates it without growing the cache.
        self._cache: Dict[Tuple, Tuple[int, Plan]] = {}
        self._lock = threading.Lock()

    def _key(self, c: Chain, env) -> Tuple:
        dims = bind_dims(c, env or {})
        struct = tuple(
            (type(op).__name__, getattr(op, "symmetric", False))
            for op in c.ops
        )
        # The policy's fingerprint (not just its registry key): a
        # parametrized discriminant (rankk's measurement budget k) folds
        # its parameters in, so two planners sharing a cache through the
        # module-level plan() helpers can never alias distinct policies.
        return (struct, dims, self._policy.fingerprint())

    def _profile_generation(self) -> int:
        """Mutation counter of the live table profile (−1: no table).

        Folding this into the memo slot is what lets a ``record=True``
        planner re-rank after online refinement: without it, the first
        plan per shape was frozen forever even when heavy refinement had
        flipped the ranking (ISSUE 4 satellite). Discriminants whose
        ranking does not read the profile (``requires_profile=False``:
        ``flops``/``roofline`` are pure arithmetic; ``measured`` re-times
        on hardware) pin the generation — otherwise every observe() would
        force a provably identical re-enumeration (or, for ``measured``,
        a fresh wall-clock timing sweep) per call.
        """
        if not self._policy.requires_profile:
            return -1
        table = self._recording_table()
        return table.generation if table is not None else -1

    def policy_fingerprint(self) -> Tuple:
        """Stable identity of the selection policy (registry key + params).

        Parametrized discriminants (``rankk``'s measurement budget) fold
        their parameters in, so two planners configured differently can
        never alias one cache slot. :mod:`repro.serve.plan_cache` folds
        this into its shape→plan key.
        """
        return self._policy.fingerprint()

    def profile_generation(self) -> int:
        """Current profile generation this planner would rank under.

        −1 when the policy never reads the profile (pure arithmetic) or
        there is no live table; otherwise the table's mutation counter.
        A bump means online refinement may have flipped rankings — plans
        memoised under an older generation are stale. This is the serving
        cache's invalidation signal (docs/serving.md).
        """
        return self._profile_generation()

    def plan(self, c: Chain, env: Optional[Dict[str, int]] = None) -> Plan:
        """Enumerate, rank, and memoise: chain + sizes → :class:`Plan`.

        Memoised per ``(structure, dims, policy fingerprint)`` and revali-
        dated against :meth:`profile_generation`, so the enumeration and
        ranking cost is paid once per shape until refinement moves the
        profile. Thread-safe; concurrent misses may race to enumerate but
        converge on one cached entry (the serving layer's
        :class:`repro.serve.plan_cache.PlanCache` adds request coalescing
        on top so same-shape misses do the work exactly once).
        """
        with span("planner.plan"):
            key = self._key(c, env)
            gen = self._profile_generation()
            with self._lock:
                hit = self._cache.get(key)
            if hit is not None and hit[0] == gen:
                return hit[1]
            with span("planner.enumerate"):
                algos = enumerate_algorithms(c, env)
            # Capability-gated arguments: a profile handed to a policy that
            # never reads one (flops/measured/roofline) is a select() error
            # now, and the planner always *has* a resolved profile — so
            # only forward it where it is meaningful.
            with span("planner.select"):
                ranked = select(
                    algos, self.discriminant,
                    profile=(self.profile if self._policy.requires_profile
                             else None),
                    dtype_bytes=self.dtype_bytes)
            best = ranked[0]
            plan = Plan(
                algorithm=best,
                fn=self.runner.build(best),
                ranked=tuple(a.name for a in ranked),
                discriminant=self.discriminant,
            )
            with self._lock:
                self._cache[key] = (gen, plan)
            return plan

    def __call__(self, c: Chain, *arrays, env=None):
        """Plan and evaluate in one call (arrays follow chain leaf order,
        with Gram-pair leaves deduplicated: pass each distinct matrix once
        per its first occurrence index). With ``record=True`` the execution
        is timed and fed back into the live profile."""
        with span("planner.call", request=True):
            plan = self.plan(c, env)
            if not self.record:
                return plan.fn(*arrays)
            out, seconds = measure_seconds(plan.fn, *arrays)
            self.observe(plan, seconds)
            return out

    # -- online refinement ------------------------------------------------
    def _recording_table(self) -> Optional[TableProfile]:
        prof = self.profile
        if isinstance(prof, TableProfile):
            return prof
        return getattr(prof, "table_profile", None)

    def observe(self, plan: Plan, seconds: float) -> None:
        """Fold one measured plan execution back into the live profile.

        The total wall time is apportioned over the plan's kernel calls in
        proportion to their current predicted times (the additive model run
        backwards), then EMA-blended into the table so noisy single
        observations don't thrash a calibrated entry. No-op when the
        profile has no table to record into (pure analytical).
        """
        table = self._recording_table()
        if table is None or seconds <= 0:
            return
        calls = plan.algorithm.calls
        if not calls:
            return
        # Apportioning weights must come from ONE model: a HybridProfile
        # mixes measured entries (this machine's scale) with analytical
        # fallbacks (TPU constants, often 100-1000× off), and proportions
        # across that mix would credit analytically-predicted calls with
        # near-zero shares — poisoning the table with "free" kernels. The
        # analytical member's *relative* kernel costs are internally
        # consistent, which is all apportioning needs.
        weight_model = getattr(self.profile, "analytical", self.profile)
        try:
            preds = [max(weight_model.time(c, self.dtype_bytes), 1e-12)
                     for c in calls]
        except KeyError:
            # Plain TableProfile with a kernel kind it has never seen
            # (e.g. an empty table being bootstrapped): weight by the
            # closed-form model instead of dying after the work is done.
            weight_model = AnalyticalTPUProfile()
            preds = [max(weight_model.time(c, self.dtype_bytes), 1e-12)
                     for c in calls]
        total = sum(preds)
        blend = self.observation_blend
        with self._lock:
            for call, pred in zip(calls, preds):
                share = seconds * pred / total
                old = table.table.get((call.kind, call.dims))
                new = share if old is None else (
                    (1.0 - blend) * old + blend * share)
                table.record(call, new)

    def save(self, directory: Optional[Path] = None) -> Optional[Path]:
        """Persist the (possibly refined) table profile to the cache.

        Uses the planner's (profile_backend, profile_dtype) key — the same
        key ``resolve_profile`` loads with, so the next process finds the
        refinements. NB a profile passed *explicitly* to the constructor is
        stamped with that key too: if you hand the planner a profile
        calibrated for a different backend/dtype, set
        ``profile_backend``/``profile_dtype`` to match its provenance or
        the cache entry will misattribute the timings.
        """
        table = self._recording_table()
        if table is None:
            return None
        fp = current_fingerprint(backend=self.profile_backend,
                                 dtype=self.profile_dtype)
        return save_profile(table, fp, directory=directory,
                            meta={"source": "planner.online_refinement"})


_default_planner: Optional[Planner] = None
_planners_by_discriminant: Dict[str, "Planner"] = {}
_default_lock = threading.Lock()


def default_planner() -> Planner:
    """Process-wide planner; auto-loads this machine's cached calibration.

    The profile tier is resolved lazily at first use (see
    :func:`resolve_profile`), so running ``python -m repro.core.calibrate``
    before process start is all it takes to upgrade every consumer from
    the analytical model to measured tables.
    """
    global _default_planner
    with _default_lock:
        if _default_planner is None:
            _default_planner = Planner()
        return _default_planner


def reset_default_planner() -> None:
    """Drop the cached process-wide planners (tests; post-calibration)."""
    global _default_planner
    with _default_lock:
        _default_planner = None
        _planners_by_discriminant.clear()


def plan(c: Chain, env: Optional[Dict[str, int]] = None,
         discriminant: str = "perfmodel") -> Plan:
    """Module-level convenience using a per-discriminant default planner.

    Planners (and their profile-cache read) are memoised per discriminant
    so repeated calls stay in-memory after the first.
    """
    p = default_planner()
    if discriminant != p.discriminant:
        with _default_lock:
            p = _planners_by_discriminant.get(discriminant)
            if p is None:
                p = Planner(discriminant=discriminant)
                _planners_by_discriminant[discriminant] = p
    return p.plan(c, env)
