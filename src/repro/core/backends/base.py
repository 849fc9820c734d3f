"""The execution-backend protocol, the generic step walker, and the registry.

The paper's anomalies are a property of the *kernel implementation*, not
the math: the same expression has different anomaly regions on MKL than on
XLA or a Pallas TPU kernel (Sankaran & Bientinesi 2022 argue discriminant
quality must be re-validated per backend). Asking "where do the backends
disagree?" therefore needs every executor to speak one interface. This
module defines it:

* :class:`KernelOps` — a backend's kernel vocabulary: one callable per
  :data:`~repro.core.flops.KERNEL_KINDS` entry (plus ``transpose``).
  Implementing these ~6 methods is the whole cost of a new backend.
* :func:`walk_steps` — the **one** DAG walker. Every executor in the
  repo used to reimplement the step loop (BLAS, numpy reference, jnp,
  Pallas); they now all walk here, parameterized by their
  :class:`KernelOps`.
* :class:`ExecutionBackend` — the protocol every backend satisfies:
  ``make_operands`` / ``execute`` / ``build`` / ``time_algorithm`` /
  ``benchmark_call`` / ``fingerprint_tags``. The base class implements
  all of them generically on top of :func:`walk_steps`; backends
  override only operand placement (``_asarray``), timing hooks
  (``_pre_rep`` for cache flushes, ``_sync`` for async dispatch) and, if
  they compile, ``_timed_callable``.
* :func:`register_backend` / :func:`get_backend` /
  :func:`registered_backends` — the registry ``calibrate``, ``sweep``,
  ``selector`` and ``planner`` resolve backends through. The registry
  key doubles as the profile/atlas fingerprint ``backend`` string, so a
  backend's measurements are never mixed with another's.

``benchmark_call`` is derived, not duplicated: a
:class:`~repro.core.flops.KernelCall` is wrapped into a one-step
:func:`synthetic_algorithm` and timed through the exact same path as
whole algorithms — the two parallel benchmark implementations the
pre-registry runners carried are gone.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...runtime.tracing import span
from ..algorithms import Algorithm, Leaf, Step
from ..flops import KernelCall


class KernelOps:
    """Per-backend kernel vocabulary the generic walker dispatches to.

    ``symm``/``symm_r`` receive the symmetric operand as ``s`` (stored as
    its lower triangle — implementations must not read above the
    diagonal) and the dense operand as ``b``; ``syrk`` returns the lower
    triangle of ``a·aᵀ`` (``tri`` storage); ``tri2full`` mirrors a lower
    triangle into a full matrix.
    """

    def transpose(self, a):
        raise NotImplementedError

    def gemm(self, a, b):
        raise NotImplementedError

    def syrk(self, a):
        raise NotImplementedError

    def symm(self, s, b):
        """S·B with S symmetric (side L)."""
        raise NotImplementedError

    def symm_r(self, b, s):
        """B·S with S symmetric (side R)."""
        raise NotImplementedError

    def tri2full(self, t):
        raise NotImplementedError

    # -- fused adjacent-step dispatch (optional) ---------------------------
    def fused_kinds(self) -> frozenset:
        """Fused step patterns this vocabulary implements.

        The walker consults this before dispatching: when two adjacent
        steps match an advertised pattern (see :func:`fusable_pattern`),
        it calls the fused method instead of the two single-kind ones.
        Default: no fusion — CPU backends and plain jnp (where XLA does
        its own fusion) keep the one-step-one-kernel mapping.
        """
        return frozenset()

    def chain_gemm(self, a, b, c):
        """Fused ``(a·b)·c`` (pattern ``"gemm+gemm"``)."""
        raise NotImplementedError

    def gemm_syrk(self, a, b):
        """Fused lower triangle of ``(a·b)(a·b)ᵀ`` (``"gemm+syrk"``)."""
        raise NotImplementedError


def _fetched_refs(step: Step) -> tuple:
    """The operand refs ``walk_steps`` actually fetches for ``step``.

    syrk/tri2full fetch only ``lhs``; a syrk step's ``rhs`` may carry a
    provenance twin (the transposed factor the builder pruned) that is
    never materialized — counting it as a use would veto valid fusions.
    """
    if step.call.kind in ("gemm", "symm"):
        return (step.lhs, step.rhs)
    return (step.lhs,)


def fusable_pattern(first: Step, second: Step,
                    rest: Sequence[Step]) -> Optional[str]:
    """Which advertised fused pattern ``(first, second)`` matches, if any.

    ``first`` must be a gemm whose output ``X`` is consumed *only* as
    ``second``'s left operand and never fetched by any later step (its
    HBM materialization is what the fusion deletes):

    * ``"gemm+gemm"`` — ``second`` is a gemm with ``lhs == X``
      (``(A·B)·C``, the :mod:`repro.kernels.chain_gemm` shape);
    * ``"gemm+syrk"`` — ``second`` is a syrk on ``X``
      (``tril((A·B)(A·B)ᵀ)``, the epilogue fusion).
    """
    if first.call.kind != "gemm":
        return None
    x = first.out
    for later in rest:
        for ref in _fetched_refs(later):
            if not isinstance(ref, Leaf) and ref == x:
                return None
    second_lhs_is_x = not isinstance(second.lhs, Leaf) and second.lhs == x
    if second.call.kind == "gemm" and second_lhs_is_x and (
            isinstance(second.rhs, Leaf) or second.rhs != x):
        return "gemm+gemm"
    if second.call.kind == "syrk" and second_lhs_is_x:
        return "gemm+syrk"
    return None


def walk_steps(steps: Sequence[Step], leaf_fetch: Callable[[int], object],
               ops: KernelOps):
    """Execute/trace an algorithm's step DAG with one backend's kernels.

    ``leaf_fetch(base)`` returns the *untransposed* operand for a leaf
    base index; transposition is applied here via ``ops.transpose`` so
    callers hand over plain per-base arrays. Works eagerly (numpy, BLAS)
    and under tracing (jit/vmap of jnp/Pallas ops) alike — this is the
    single step walker the ISSUE-4 refactor collapsed the four previous
    executors into.

    When ``ops.fused_kinds()`` advertises fused patterns, adjacent steps
    matching :func:`fusable_pattern` dispatch to the fused method
    (``ops.chain_gemm`` / ``ops.gemm_syrk``) as one launch; the fused
    intermediate is provably dead (the pattern check rejects any later
    use), so only the second step's output id is bound.

    Each kernel call runs in a ``repro.step.<method>`` span, and each
    transposed leaf in ``repro.step.transpose`` (:mod:`repro.runtime.tracing`).
    """
    inter: Dict[int, object] = {}

    def fetch(ref):
        if isinstance(ref, Leaf):
            a = leaf_fetch(ref.base)
            if not ref.transposed:
                return a
            with span("step.transpose"):
                return ops.transpose(a)
        return inter[ref]

    fused = ops.fused_kinds()
    out = None
    i = 0
    n = len(steps)
    while i < n:
        step = steps[i]
        if fused and i + 1 < n:
            pattern = fusable_pattern(step, steps[i + 1], steps[i + 2:])
            if pattern is not None and pattern in fused:
                nxt = steps[i + 1]
                if pattern == "gemm+gemm":
                    a, b, c = fetch(step.lhs), fetch(step.rhs), fetch(nxt.rhs)
                    with span("step.chain_gemm"):
                        out = ops.chain_gemm(a, b, c)
                else:
                    a, b = fetch(step.lhs), fetch(step.rhs)
                    with span("step.gemm_syrk"):
                        out = ops.gemm_syrk(a, b)
                inter[nxt.out] = out
                i += 2
                continue
        kind = step.call.kind
        if kind == "gemm":
            a, b = fetch(step.lhs), fetch(step.rhs)
            with span("step.gemm"):
                out = ops.gemm(a, b)
        elif kind == "syrk":
            a = fetch(step.lhs)
            with span("step.syrk"):
                out = ops.syrk(a)
        elif kind == "symm":
            a, b = fetch(step.lhs), fetch(step.rhs)
            if step.symm_side == "R":
                with span("step.symm_r"):
                    out = ops.symm_r(a, b)
            else:
                with span("step.symm"):
                    out = ops.symm(a, b)
        elif kind == "tri2full":
            a = fetch(step.lhs)
            with span("step.tri2full"):
                out = ops.tri2full(a)
        else:
            raise ValueError(kind)
        inter[step.out] = out
        i += 1
    return out


def num_inputs(alg: Algorithm) -> int:
    """Positional arity of a built callable: max leaf *index* + 1.

    The callable's signature follows chain positions; only *base*
    positions are ever read (a Gram pair's ``A`` and ``Aᵀ`` share one
    array), so callers may pass any placeholder at non-base slots.
    """
    mx = -1
    for step in alg.steps:
        for ref in (step.lhs, step.rhs):
            if isinstance(ref, Leaf):
                mx = max(mx, ref.index)
    return mx + 1


def synthetic_algorithm(call: KernelCall) -> Algorithm:
    """A one-step algorithm exercising exactly one kernel call.

    This is what makes ``benchmark_call`` generic: isolated kernel
    benchmarks run through the same ``make_operands`` →
    ``time_algorithm`` path as whole algorithms, so no backend carries a
    second, parallel per-kind benchmarking switch.
    """
    if call.kind == "gemm":
        m, n, k = call.dims
        a = Leaf(index=0, base=0, transposed=False, rows=m, cols=k)
        b = Leaf(index=1, base=1, transposed=False, rows=k, cols=n)
        step = Step(call=call, lhs=a, rhs=b, out=0, out_rows=m, out_cols=n,
                    out_storage="full", out_symmetric=False)
    elif call.kind == "syrk":
        m, k = call.dims
        a = Leaf(index=0, base=0, transposed=False, rows=m, cols=k)
        step = Step(call=call, lhs=a, rhs=None, out=0, out_rows=m,
                    out_cols=m, out_storage="tri", out_symmetric=True)
    elif call.kind == "symm":
        m, n = call.dims
        s = Leaf(index=0, base=0, transposed=False, rows=m, cols=m,
                 symmetric=True)
        b = Leaf(index=1, base=1, transposed=False, rows=m, cols=n)
        step = Step(call=call, lhs=s, rhs=b, out=0, out_rows=m, out_cols=n,
                    out_storage="full", out_symmetric=False)
    elif call.kind == "tri2full":
        (m,) = call.dims
        t = Leaf(index=0, base=0, transposed=False, rows=m, cols=m,
                 storage="tri")
        step = Step(call=call, lhs=t, rhs=None, out=0, out_rows=m,
                    out_cols=m, out_storage="full", out_symmetric=True)
    else:
        raise ValueError(call.kind)
    return Algorithm(name=f"bench_{call.kind}", steps=(step,))


def synthetic_fused_algorithm(kind: str, dims: Sequence[int]) -> Algorithm:
    """A two-step algorithm exercising exactly one fused pattern.

    The fused analogue of :func:`synthetic_algorithm`: the step pair is
    built so :func:`fusable_pattern` matches, and a fusion-advertising
    backend times the fused launch while any other backend times the
    two-kernel form — the same Algorithm measures both sides of the
    fusion trade.

    * ``"chain_gemm"``, dims ``(m, k, l, n)`` — ``(A·B)·C`` with
      A ``(m,k)``, B ``(k,l)``, C ``(l,n)``;
    * ``"gemm_syrk"``, dims ``(m, k, l)`` — ``tril((A·B)(A·B)ᵀ)`` with
      A ``(m,k)``, B ``(k,l)``.
    """
    if kind == "chain_gemm":
        m, k, l, n = dims
        a = Leaf(index=0, base=0, transposed=False, rows=m, cols=k)
        b = Leaf(index=1, base=1, transposed=False, rows=k, cols=l)
        c = Leaf(index=2, base=2, transposed=False, rows=l, cols=n)
        s1 = Step(call=KernelCall("gemm", (m, l, k)), lhs=a, rhs=b, out=0,
                  out_rows=m, out_cols=l, out_storage="full",
                  out_symmetric=False)
        s2 = Step(call=KernelCall("gemm", (m, n, l)), lhs=0, rhs=c, out=1,
                  out_rows=m, out_cols=n, out_storage="full",
                  out_symmetric=False)
    elif kind == "gemm_syrk":
        m, k, l = dims
        a = Leaf(index=0, base=0, transposed=False, rows=m, cols=k)
        b = Leaf(index=1, base=1, transposed=False, rows=k, cols=l)
        s1 = Step(call=KernelCall("gemm", (m, l, k)), lhs=a, rhs=b, out=0,
                  out_rows=m, out_cols=l, out_storage="full",
                  out_symmetric=False)
        s2 = Step(call=KernelCall("syrk", (m, l)), lhs=0, rhs=None, out=1,
                  out_rows=m, out_cols=m, out_storage="tri",
                  out_symmetric=True)
    else:
        raise ValueError(
            f"unknown fused pattern {kind!r}; expected 'chain_gemm' or "
            f"'gemm_syrk'")
    return Algorithm(name=f"bench_{kind}", steps=(s1, s2))


class ExecutionBackend:
    """Base class + protocol for one way of executing algorithms.

    Subclasses set ``name`` (the registry key — also the fingerprint
    ``backend`` string for profiles and atlases), ``default_dtype``,
    ``dtypes`` (``None`` = any) and ``shard_mode`` (``"process"`` for
    GIL/cache-bound CPU backends the sweep engine isolates in worker
    processes, ``"device"`` for backends the engine shards across JAX
    devices), then override the hooks they need:

    * ``ops()``            — the :class:`KernelOps` (required);
    * ``_asarray(a)``      — dtype/layout/device placement of operands;
    * ``_pre_rep()``       — per-repetition setup (BLAS cache flush);
    * ``_sync(out)``       — block on async dispatch (JAX);
    * ``_timed_callable()``— what ``time_algorithm`` times (JAX jits).
    """

    name: str = "abstract"
    default_dtype: str = "float64"
    #: Allowed dtype labels; ``None`` means any.
    dtypes: Optional[Tuple[str, ...]] = None
    shard_mode: str = "process"

    def __init__(self, reps: int = 3, dtype: Optional[str] = None,
                 rng: Optional[np.random.Generator] = None,
                 seed: Optional[int] = None):
        dtype = dtype or self.default_dtype
        if self.dtypes is not None and dtype not in self.dtypes:
            raise ValueError(
                f"backend {self.name!r} measures {'/'.join(self.dtypes)}; "
                f"got dtype={dtype!r} — a different label would stamp a "
                f"fingerprint the measurements don't match")
        self.reps = reps
        self.dtype = dtype
        #: With ``seed`` set, each leaf operand's content is a pure
        #: function of ``(seed, base, shape)`` — identical across reruns,
        #: shards, pool workers, and the arena fast path. With it unset
        #: (legacy default), draws come from ``rng`` in call order.
        self.seed = seed
        self.rng = rng or np.random.default_rng(0)

    # -- subclass hooks ---------------------------------------------------
    def ops(self) -> KernelOps:
        raise NotImplementedError

    def _asarray(self, a: np.ndarray):
        """Place one freshly synthesized operand (dtype/layout/device)."""
        return a

    def _pre_rep(self) -> None:
        """Per-repetition setup before the timer starts (cache flush)."""

    def _sync(self, out):
        """Block until ``out`` is materialized (async-dispatch backends)."""
        return out

    def _timed_callable(self, alg: Algorithm, operands: Dict[int, object]
                        ) -> Callable[[], object]:
        """The zero-arg callable ``time_algorithm`` times per repetition."""
        return lambda: self.execute(alg, operands)

    # -- the protocol ------------------------------------------------------
    def fingerprint_tags(self) -> Tuple[str, str]:
        """(backend, dtype) labels profiles/atlases are keyed by."""
        return (self.name, self.dtype)

    def make_operands(self, alg: Algorithm) -> Dict[int, object]:
        """Fresh random inputs for every distinct leaf *base* of ``alg``.

        Leaves are stored untransposed (transposition is applied at fetch
        by the walker); symmetric leaves are symmetrized, since SYMM-based
        algorithms read only a triangle and would otherwise disagree with
        their GEMM-based siblings.
        """
        out: Dict[int, object] = {}
        for step in alg.steps:
            for ref in (step.lhs, step.rhs):
                if isinstance(ref, Leaf) and ref.base not in out:
                    out[ref.base] = self.make_leaf_operand(ref)
        return out

    def make_leaf_operand(self, ref: Leaf) -> object:
        """One leaf's operand buffer (untransposed, symmetrized, placed).

        This is the unit the operand arena pools: with ``seed`` set the
        buffer depends only on ``(seed, base, shape)``, so arena-served
        and freshly synthesized operands are bit-identical and sharded
        reruns replay exactly.
        """
        r, c = (ref.cols, ref.rows) if ref.transposed else (
            ref.rows, ref.cols)
        rng = self.rng if self.seed is None else np.random.default_rng(
            (self.seed, ref.base, r, c))
        a = rng.standard_normal((r, c))
        if ref.symmetric:
            a = (a + a.T) / 2.0
        return self._asarray(a)

    def execute(self, alg: Algorithm,
                operands: Dict[int, object]):
        """Evaluate ``alg`` on base-indexed operands via the one walker."""
        return walk_steps(alg.steps, operands.__getitem__, self.ops())

    def build(self, alg: Algorithm) -> Callable:
        """A positional callable ``fn(*inputs)`` evaluating ``alg``.

        Inputs follow chain leaf order (see :func:`num_inputs`); for JAX
        backends the result is jit-able, for CPU backends it is a plain
        closure — either way the planner can embed it.
        """
        ops = self.ops()
        steps = alg.steps

        def fn(*inputs):
            return walk_steps(steps, inputs.__getitem__, ops)

        return fn

    def time_algorithm(self, alg: Algorithm,
                       operands: Optional[Dict[int, object]] = None,
                       reps: Optional[int] = None) -> float:
        """Median-of-reps wall seconds (warm-up excluded, dispatch synced).

        The protocol knobs live on the instance: BLAS-style backends flush
        the cache in ``_pre_rep`` (paper §3.4), JAX backends jit in
        ``_timed_callable`` and block in ``_sync``. The warm-up and each rep
        run in a ``repro.backend.call`` span, which encloses the clock reads
        so that the times recorded do not include its cost.
        """
        if operands is None:
            operands = self.make_operands(alg)
        reps = self.reps if reps is None else reps
        fn = self._timed_callable(alg, operands)
        with span("backend.call"):
            self._sync(fn())  # warm-up: library init / compile / page-in
        ts: List[float] = []
        for _ in range(reps):
            self._pre_rep()
            with span("backend.call"):
                t0 = time.perf_counter()
                self._sync(fn())
                ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    def benchmark_call(self, call: KernelCall,
                       reps: Optional[int] = None) -> float:
        """Time one kernel call in isolation (synthetic one-step algorithm).

        Same repetition/flush/sync protocol as :meth:`time_algorithm` —
        by construction, since it *is* ``time_algorithm`` on a
        :func:`synthetic_algorithm`.
        """
        return self.time_algorithm(synthetic_algorithm(call), reps=reps)

    def num_inputs(self, alg: Algorithm) -> int:
        return num_inputs(alg)


# ---------------------------------------------------------------- registry --

_REGISTRY: Dict[str, Callable[..., ExecutionBackend]] = {}


def register_backend(name: str, factory: Callable[..., ExecutionBackend],
                     ) -> Callable[..., ExecutionBackend]:
    """Register a backend class/factory under ``name`` (the fingerprint key).

    Returns ``factory`` so it can be used as a decorator. Duplicate names
    are rejected: silently shadowing ``blas`` would re-key every cached
    profile and atlas on disk.
    """
    key = name.lower()
    if key in _REGISTRY:
        raise ValueError(f"execution backend {key!r} is already registered")
    _REGISTRY[key] = factory
    return factory


def get_backend_class(name: str) -> Callable[..., ExecutionBackend]:
    """Resolve a registry name to its backend class/factory."""
    try:
        return _REGISTRY[name.lower()]
    except KeyError:
        raise KeyError(
            f"unknown execution backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def get_backend(name: str, **options) -> ExecutionBackend:
    """Instantiate a registered backend (strict: unknown options raise)."""
    return get_backend_class(name)(**options)


def make_backend(name: str, **options) -> ExecutionBackend:
    """CLI-lenient :func:`get_backend`: drops options the backend lacks.

    Generic front-ends (sweep/calibrate CLIs) pass one option superset —
    ``reps``/``flush_cache``/``dtype``/``device`` — and each backend takes
    what its constructor declares; e.g. ``flush_cache`` reaches BLAS but
    not JAX. Module-level (and so picklable inside ``functools.partial``)
    for the process-pool sweep path.
    """
    import inspect

    cls = get_backend_class(name)
    try:
        sig = inspect.signature(cls)
    except (TypeError, ValueError):  # pragma: no cover - exotic factory
        return cls(**options)
    params = sig.parameters
    if not any(p.kind is inspect.Parameter.VAR_KEYWORD
               for p in params.values()):
        options = {k: v for k, v in options.items() if k in params}
    return cls(**options)


def registered_backends() -> List[str]:
    return sorted(_REGISTRY)


def backend_default_dtype(name: str) -> str:
    """Default fingerprint dtype of a registered backend."""
    return getattr(get_backend_class(name), "default_dtype", "float32")


def backend_shard_mode(name: str) -> str:
    """How the sweep engine fans this backend out: process | device."""
    return getattr(get_backend_class(name), "shard_mode", "process")


def measure_seconds(fn: Callable, *args) -> tuple:
    """Run ``fn(*args)``, blocking on JAX async dispatch; (result, secs).

    Used by the planner's online refinement so the recorded time reflects
    device completion rather than dispatch-queue insertion. Deferred
    device errors surfaced by the block propagate — recording the
    dispatch-only time of a failed computation would poison the profile.
    """
    try:
        import jax
    except Exception:  # pragma: no cover - jax is a hard dep in practice
        jax = None
    t0 = time.perf_counter()
    out = fn(*args)
    if jax is not None:
        jax.block_until_ready(out)  # no-op for non-JAX leaves
    return out, time.perf_counter() - t0
