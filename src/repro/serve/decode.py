"""Serving steps: batched prefill + single-token decode (greedy/temperature).

``serve_step`` is what the decode shape cells lower: one new token against
a KV/SSM cache of ``seq_len`` per sequence. The surrounding projection
chains of a 1-token step are exactly the skinny-GEMM regime where the
paper's FLOPs-vs-efficiency divergence is largest (an (1×d)·(d×V) product
runs at a tiny fraction of MXU peak, so algorithm choice is dominated by
the efficiency profile, not FLOPs).

Planner integration (docs/serving.md): the decode attention tail consults
the serving plan cache at *trace* time (``attention.pv_wo_output``), so
:func:`plan_warmup` pre-populates the cache for a model's decode shapes
before the first request traces — first-token latency then never includes
an enumeration. :func:`generate` feeds per-step wall times to an optional
:class:`~repro.runtime.supervisor.StragglerMonitor`; whole-step times are
deliberately NOT folded into kernel tables (apportioning a step across
one GEMM's calls would poison the profile — per-plan refinement happens
in :meth:`repro.serve.plan_cache.PlanService.execute`).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import api
from repro.models.transformer import ModelConfig
from repro.runtime.supervisor import StragglerMonitor


def plan_warmup(cfg: ModelConfig, max_s: int) -> List[Tuple[str, Tuple]]:
    """Pre-plan the zoo families a decode step of ``cfg`` will consult.

    Returns the (family, dims) pairs warmed so callers can log them.
    No-op (empty list) when the planner consult is disabled via
    ``REPRO_SERVE_PLANNER=0`` or the model has no attention layers.
    """
    from repro.serve.plan_cache import default_plan_service, planner_enabled
    if not planner_enabled():
        return []
    shapes: List[Tuple[str, Tuple]] = []
    if cfg.n_heads and cfg.head_dim:
        # attention.pv_wo_output's trace-time consult, per-head view.
        shapes.append(("decattn", (1, max_s, cfg.head_dim, cfg.d_model)))
        shapes.append(("decproj", (1, cfg.d_model,
                                   cfg.n_heads * cfg.head_dim)))
    if cfg.d_ff:
        shapes.append(("decmlp", (1, cfg.d_model, cfg.d_ff)))
    shapes.append(("decproj", (1, cfg.d_model, cfg.vocab)))  # logits
    default_plan_service().warmup(shapes)
    return shapes


class ServeState(NamedTuple):
    caches: Any
    last_tokens: jax.Array    # (B, 1)
    rng: jax.Array


def serve_step(state: ServeState, params: Any, *, cfg: ModelConfig,
               temperature: float = 0.0
               ) -> Tuple[ServeState, jax.Array]:
    """One decode step for the whole batch → (new state, next tokens)."""
    logits, caches = api.decode_step(params, cfg, state.last_tokens,
                                     state.caches)
    logits = logits[:, -1, :]
    if temperature > 0:
        rng, sub = jax.random.split(state.rng)
        nxt = jax.random.categorical(sub, logits / temperature, axis=-1)
    else:
        rng = state.rng
        nxt = jnp.argmax(logits, axis=-1)
    nxt = nxt[:, None].astype(jnp.int32)
    return ServeState(caches=caches, last_tokens=nxt, rng=rng), nxt


def make_serve_step(cfg: ModelConfig, **kw):
    return functools.partial(serve_step, cfg=cfg, **kw)


def generate(params: Any, cfg: ModelConfig, prompt: jax.Array,
             max_new: int, max_s: Optional[int] = None,
             batch_inputs: Optional[Dict[str, Any]] = None,
             temperature: float = 0.0, seed: int = 0,
             monitor: Optional[StragglerMonitor] = None) -> jax.Array:
    """Greedy/temperature generation: prompt (B, S0) → (B, S0 + max_new).

    Prefill fills the caches token-by-token for cache-correct semantics on
    every family (attention archs could batch-prefill; the SSM/hybrid
    single-step path is exact for all).

    The plan cache is warmed for this config's decode shapes before the
    first trace (:func:`plan_warmup`). Pass a ``monitor`` to feed decode
    step wall times into a straggler watchdog — generation itself never
    writes step times into kernel profiles (see module docstring).
    """
    b, s0 = prompt.shape
    max_s = max_s or (s0 + max_new + 1)
    plan_warmup(cfg, max_s)
    caches = api.init_caches(params, cfg, b, max_s,
                             batch_inputs=batch_inputs)
    state = ServeState(caches=caches,
                       last_tokens=prompt[:, :1],
                       rng=jax.random.PRNGKey(seed))
    # Donated state: each step writes its caches over the ones it read.
    # Without it, async dispatch keeps up to three cache generations live
    # (phi3-mini at batch 4 and a 2,048-token cache ran out of v5e HBM).
    step = jax.jit(make_serve_step(cfg, temperature=temperature),
                   donate_argnums=(0,))
    out = [prompt]
    # Teacher-forced prefill: feed prompt tokens, ignore predictions.
    for i in range(s0 - 1):
        state, _ = step(state, params)
        state = state._replace(last_tokens=prompt[:, i + 1: i + 2])
    gen = []
    for n in range(max_new):
        t0 = time.perf_counter()
        state, nxt = step(state, params)
        if monitor is not None:
            jax.block_until_ready(nxt)
            monitor.observe(n, time.perf_counter() - t0)
        gen.append(nxt)
    return jnp.concatenate(out + gen, axis=1)
