"""GQA attention with full / flash / sliding-window variants + KV cache.

Three execution paths:
  * ``train``  — full sequence, causal (or bidirectional for encoders);
    uses the Pallas flash kernel when the sequence is block-divisible and
    flash is requested, else the masked-dense reference.
  * ``prefill`` — same as train but returns the KV cache.
  * ``decode`` — one new token against a cache: a dense (1, S) contraction;
    quadratic blocking is pointless here, so it is pure jnp (and is where
    the LAMP chain planner acts on the surrounding projections instead).

No torch-style module state: ``init`` returns (params, axes); ``apply_*``
are pure functions.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import scan_util
from .layers import Axes, Params, apply_rope, dense, dense_init, softcap


class AttnConfig(NamedTuple):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    logit_softcap: float = 0.0      # gemma2: 50.0
    window: int = 0                 # 0 = global; >0 = sliding window
    causal: bool = True
    use_flash: bool = True
    query_pre_scale: Optional[float] = None  # gemma2 scales by head_dim**-.5


class KVCache(NamedTuple):
    k: jax.Array        # (B, max_s, Hkv, Dh)
    v: jax.Array        # (B, max_s, Hkv, Dh)
    length: jax.Array   # () int32 — tokens currently valid


def init(key: jax.Array, cfg: AttnConfig, dtype=jnp.float32
         ) -> Tuple[Params, Axes]:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p: Params = {}
    a: Axes = {}
    p["wq"], a["wq"] = dense_init(
        kq, cfg.d_model, cfg.n_heads * cfg.head_dim, ("embed", "heads"),
        dtype)
    p["wk"], a["wk"] = dense_init(
        kk, cfg.d_model, cfg.n_kv_heads * cfg.head_dim, ("embed", "kv_heads"),
        dtype)
    p["wv"], a["wv"] = dense_init(
        kv, cfg.d_model, cfg.n_kv_heads * cfg.head_dim, ("embed", "kv_heads"),
        dtype)
    p["wo"], a["wo"] = dense_init(
        ko, cfg.n_heads * cfg.head_dim, cfg.d_model, ("heads", "embed"),
        dtype)
    return p, a


def _project_qkv(params: Params, cfg: AttnConfig, x: jax.Array,
                 positions: jax.Array, rope: Optional[Tuple]):
    from repro.sharding.context import shard_heads
    b, s, _ = x.shape
    q = dense(params["wq"], x).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = dense(params["wk"], x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = dense(params["wv"], x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    # Megatron TP layout: heads sharded, sequence gathered (no-op when the
    # head count doesn't divide the model axis, or outside a mesh context).
    q = shard_heads(q)
    k = shard_heads(k)
    v = shard_heads(v)
    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
    return q, k, v


def _dense_attention(cfg: AttnConfig, q, k, v, *, q_offset=0) -> jax.Array:
    """Masked dense attention; q: (B,Sq,H,Dh), k/v: (B,Sk,Hkv,Dh)."""
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    group = h // k.shape[2]
    scale = cfg.query_pre_scale or dh ** -0.5
    kq = jnp.repeat(k, group, axis=2)
    vq = jnp.repeat(v, group, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kq,
                        preferred_element_type=jnp.float32) * scale
    logits = softcap(logits, cfg.logit_softcap)
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if cfg.causal:
        mask &= qpos >= kpos
    if cfg.window > 0:
        mask &= qpos - kpos < cfg.window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(vq.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vq)
    return out


def _attn_mask(sq, block, start, causal, window, qpos):
    kpos = start + jnp.arange(block)
    mask = jnp.ones((sq, block), dtype=bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        mask &= qpos[:, None] - kpos[None, :] < window
    return mask


def _chunked_fwd_scan(q, kb, vb, starts, *, scale, causal, window,
                      logit_softcap, block):
    b, sq, h, dh = q.shape
    qf = jnp.swapaxes(q.astype(jnp.float32), 1, 2)   # (B,H,Sq,Dh)
    qpos = jnp.arange(sq)

    def body(carry, inp):
        acc, m_prev, l_prev = carry
        kblk, vblk, start = inp
        s = jnp.einsum("bhqd,bkhd->bhqk", qf, kblk.astype(jnp.float32)
                       ) * scale
        s = softcap(s, logit_softcap)
        mask = _attn_mask(sq, block, start, causal, window, qpos)
        s = jnp.where(mask[None, None], s, -1e30)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32))
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, h, sq, dh), jnp.float32)
    m0 = jnp.full((b, h, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, m, l), _ = scan_util.scan(body, (acc0, m0, l0), (kb, vb, starts))
    l = jnp.maximum(l, 1e-30)
    out = acc / l[..., None]
    lse = m + jnp.log(l)
    return out, lse                                   # (B,H,Sq,Dh), (B,H,Sq)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7))
def _chunked_core(q, kq, vq, scale, causal, window, logit_softcap, block):
    """Flash-style attention with a hand-written VJP: the backward pass
    recomputes P blockwise from (q, k, v, lse) instead of saving the S×S
    probability tensor — O(S·block) memory in both directions. Softcap is
    supported in forward-only paths; the VJP assumes softcap == 0 (gemma2
    training uses the dense path below the chunk threshold)."""
    b, sq, h, dh = q.shape
    nb = kq.shape[1] // block
    kb = jnp.moveaxis(kq.reshape(b, nb, block, h, dh), 1, 0)
    vb = jnp.moveaxis(vq.reshape(b, nb, block, h, dh), 1, 0)
    starts = jnp.arange(nb) * block
    out, _ = _chunked_fwd_scan(q, kb, vb, starts, scale=scale,
                               causal=causal, window=window,
                               logit_softcap=logit_softcap, block=block)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)    # (B,Sq,H,Dh)


def _chunked_core_fwd(q, kq, vq, scale, causal, window, logit_softcap,
                      block):
    b, sq, h, dh = q.shape
    nb = kq.shape[1] // block
    kb = jnp.moveaxis(kq.reshape(b, nb, block, h, dh), 1, 0)
    vb = jnp.moveaxis(vq.reshape(b, nb, block, h, dh), 1, 0)
    starts = jnp.arange(nb) * block
    out, lse = _chunked_fwd_scan(q, kb, vb, starts, scale=scale,
                                 causal=causal, window=window,
                                 logit_softcap=logit_softcap, block=block)
    res = (q, kq, vq, out, lse)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype), res


def _chunked_core_bwd(scale, causal, window, logit_softcap, block,
                      res, g):
    q, kq, vq, out, lse = res
    b, sq, h, dh = q.shape
    nb = kq.shape[1] // block
    qf = jnp.swapaxes(q.astype(jnp.float32), 1, 2)     # (B,H,Sq,Dh)
    gf = jnp.swapaxes(g.astype(jnp.float32), 1, 2)     # (B,H,Sq,Dh)
    kb = jnp.moveaxis(kq.reshape(b, nb, block, h, dh), 1, 0)
    vb = jnp.moveaxis(vq.reshape(b, nb, block, h, dh), 1, 0)
    starts = jnp.arange(nb) * block
    qpos = jnp.arange(sq)
    # D_i = Σ_d dout_i · out_i  (flash backward identity)
    delta = jnp.sum(gf * out, axis=-1)                 # (B,H,Sq)

    def body(dq, inp):
        kblk, vblk, start = inp
        kf = kblk.astype(jnp.float32)
        vf = vblk.astype(jnp.float32)
        s = jnp.einsum("bhqd,bkhd->bhqk", qf, kf) * scale
        if logit_softcap > 0:
            t = jnp.tanh(s / logit_softcap)
            s_used = logit_softcap * t
        else:
            s_used = s
        mask = _attn_mask(sq, block, start, causal, window, qpos)
        s_used = jnp.where(mask[None, None], s_used, -1e30)
        p = jnp.exp(s_used - lse[..., None])           # (B,H,Sq,block)
        dv = jnp.einsum("bhqk,bhqd->bkhd", p, gf)
        dp = jnp.einsum("bhqd,bkhd->bhqk", gf, vf)
        ds = p * (dp - delta[..., None])               # ∂L/∂s_used
        if logit_softcap > 0:
            ds = ds * (1.0 - t * t)                    # softcap chain rule
        ds = ds * scale
        dq = dq + jnp.einsum("bhqk,bkhd->bhqd", ds, kf)
        dk = jnp.einsum("bhqk,bhqd->bkhd", ds, qf)
        # Per-block dk/dv in bf16: under sequence parallelism these partial
        # sums cross the model axis (all-reduce) — halving their width
        # halves the dominant attention-backward collective (§Perf).
        return dq, (dk.astype(jnp.bfloat16), dv.astype(jnp.bfloat16))

    dq0 = jnp.zeros_like(qf)
    dq, (dks, dvs) = scan_util.scan(body, dq0, (kb, vb, starts))
    dq = jnp.swapaxes(dq, 1, 2).astype(q.dtype)
    dk = jnp.moveaxis(dks, 0, 1).reshape(b, nb * block, h, dh)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(b, nb * block, h, dh)
    return dq, dk.astype(kq.dtype), dv.astype(vq.dtype)


_chunked_core.defvjp(_chunked_core_fwd, _chunked_core_bwd)


def chunked_attention(cfg: AttnConfig, q, k, v, block: int = 512
                      ) -> jax.Array:
    """Flash-style attention with custom VJP (O(S·block) memory fwd+bwd).

    The autodiff-able counterpart of the Pallas flash kernel; XLA fuses the
    scan body into a flash-like schedule on TPU. GQA heads are broadcast
    (repeat) before the core; gradient flows back through the repeat to the
    shared KV heads automatically.
    """
    from repro.sharding.context import shard_heads
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    assert sk % block == 0, (sk, block)
    group = h // k.shape[2]
    scale = cfg.query_pre_scale or dh ** -0.5
    kq = shard_heads(jnp.repeat(k, group, axis=2))   # (B, Sk, H, Dh)
    vq = shard_heads(jnp.repeat(v, group, axis=2))
    return _chunked_core(q, kq, vq, scale, cfg.causal, cfg.window,
                         cfg.logit_softcap, block)


# Sequence length above which training uses the chunked (flash-style)
# attention instead of materializing the S×S logits.
CHUNKED_THRESHOLD = 2048


def apply_train(params: Params, cfg: AttnConfig, x: jax.Array,
                rope: Optional[Tuple] = None,
                positions: Optional[jax.Array] = None,
                return_kv: bool = False,
                differentiable: bool = True):
    """Full-sequence attention (training / prefill compute).

    ``differentiable=False`` (inference prefill) routes through the Pallas
    flash kernel; training uses the chunked scan (has a VJP) above the
    memory threshold, dense below it.
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(params, cfg, x, positions, rope)
    use_flash = (not differentiable and cfg.use_flash
                 and s % 128 == 0 and s >= 256)
    if use_flash:
        from repro.kernels import ops as kops
        scale = cfg.query_pre_scale or cfg.head_dim ** -0.5
        out = kops.flash_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
            causal=cfg.causal, scale=scale,
            logit_softcap=cfg.logit_softcap, window=cfg.window,
        ).swapaxes(1, 2)
    elif s >= CHUNKED_THRESHOLD and s % 512 == 0:
        out = chunked_attention(cfg, q, k, v)
    else:
        out = _dense_attention(cfg, q, k, v)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    proj = dense(params["wo"], out)
    if return_kv:
        return proj, (k, v)
    return proj


def init_cache(cfg: AttnConfig, batch: int, max_s: int,
               dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, max_s, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        length=jnp.zeros((), jnp.int32),
    )


def apply_prefill(params: Params, cfg: AttnConfig, x: jax.Array,
                  cache: KVCache, rope: Optional[Tuple] = None
                  ) -> Tuple[jax.Array, KVCache]:
    b, s, _ = x.shape
    proj, (k, v) = apply_train(params, cfg, x, rope=rope, return_kv=True,
                               differentiable=False)
    new_cache = KVCache(
        k=jax.lax.dynamic_update_slice(
            cache.k, k.astype(cache.k.dtype), (0, 0, 0, 0)),
        v=jax.lax.dynamic_update_slice(
            cache.v, v.astype(cache.v.dtype), (0, 0, 0, 0)),
        length=jnp.asarray(s, jnp.int32),
    )
    return proj, new_cache


def planned_pv_right_first(t: int, s: int, head_dim: int,
                           d_model: int) -> bool:
    """Trace-time planner consult: associate decode P·V·Wo right-first?

    The decode value→output tail is a genuine 3-matrix chain per head —
    P (t×s) · V (s×head_dim) · Wo (head_dim×d_model), the ``decattn`` zoo
    family — with two association orders. This asks the serving plan
    cache (:mod:`repro.serve.plan_cache`) which order the configured
    discriminant ranks first. It runs at *trace* time (shapes here are
    static Python ints), so under ``jax.jit`` the consult is amortised by
    the XLA compile cache: zero per-token cost.

    The ``REPRO_SERVE_PLANNER=0`` kill-switch keeps the left association
    the pre-planner code always used; a planner failure raises. For
    realistic decode geometries (t=1, head_dim ≤ d_model) every shipped
    policy picks left — right costs s·head_dim·d_model multiply–adds per
    head vs left's s·head_dim — so the consult leaves decode numerics
    alone there; only shapes where a cost model genuinely prefers right
    (e.g. quantization effects on degenerate head_dim > d_model layouts,
    or wide speculative-decoding chunks) switch, and both orders are
    allclose up to float reassociation.
    """
    from repro.serve.plan_cache import default_plan_service, planner_enabled
    if not planner_enabled():
        return False
    plan = default_plan_service().lookup("decattn", (t, s, head_dim, d_model))
    first = plan.algorithm.calls[0]
    # Right-first iff the first GEMM is V·Wo (its rows are the s axis).
    return s != t and first.dims[0] == s


def pv_wo_output(p_attn: jax.Array, vq: jax.Array, wo_params: Params,
                 n_heads: int, head_dim: int, out_dtype) -> jax.Array:
    """Decode value→output tail with planner-chosen association order.

    ``p_attn`` (B, H, 1, K) are the softmax probabilities, ``vq``
    (B, K, H, head_dim) the head-expanded cached values; returns the
    projected output (B, 1, d_model). Left association is the classic
    ``(P·V)·Wo``; right reshapes Wo to (H, head_dim, d_model) and applies
    it per head first. Both orders contract the same operands, so the
    result is identical up to float reassociation.
    """
    b = p_attn.shape[0]
    d_model = wo_params["w"].shape[1]
    s = vq.shape[1]
    if planned_pv_right_first(1, s, head_dim, d_model):
        wo3 = wo_params["w"].astype(p_attn.dtype).reshape(
            n_heads, head_dim, d_model)
        vwo = jnp.einsum("bkhd,hde->bkhe", vq.astype(p_attn.dtype), wo3)
        out = jnp.einsum("bhqk,bkhe->bqe", p_attn, vwo)
        return out.astype(out_dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p_attn, vq.astype(p_attn.dtype))
    out = out.reshape(b, 1, n_heads * head_dim)
    return dense(wo_params, out.astype(out_dtype))


def apply_decode(params: Params, cfg: AttnConfig, x: jax.Array,
                 cache: KVCache, rope: Optional[Tuple] = None
                 ) -> Tuple[jax.Array, KVCache]:
    """One-token step: x (B, 1, d). Cache updated in place at ``length``.

    The value→output tail P·V·Wo routes through :func:`pv_wo_output`,
    whose association order is chosen by the serving planner at trace
    time (see docs/serving.md)."""
    b, s1, _ = x.shape
    assert s1 == 1
    pos = jnp.broadcast_to(cache.length, (b, 1))
    q, k, v = _project_qkv(params, cfg, x, pos, rope)
    idx = cache.length
    new_k = jax.lax.dynamic_update_slice(
        cache.k, k.astype(cache.k.dtype), (0, idx, 0, 0))
    new_v = jax.lax.dynamic_update_slice(
        cache.v, v.astype(cache.v.dtype), (0, idx, 0, 0))
    max_s = cache.k.shape[1]
    group = cfg.n_heads // cfg.n_kv_heads
    scale = cfg.query_pre_scale or cfg.head_dim ** -0.5

    # The cache quantizes *storage* only (bf16 k/v): the contraction runs
    # at activation precision. Downcasting the fresh q or the softmax
    # probabilities to the cache dtype would double the quantization error
    # and drift decode logits away from the teacher-forced forward pass.
    kq = jnp.repeat(new_k, group, axis=2)   # (B, max_s, H, Dh)
    vq = jnp.repeat(new_v, group, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kq.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    logits = softcap(logits, cfg.logit_softcap)
    kpos = jnp.arange(max_s)
    mask = kpos[None, :] <= idx
    if cfg.window > 0:
        mask &= kpos[None, :] > idx - cfg.window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    proj = pv_wo_output(p, vq, params["wo"], cfg.n_heads, cfg.head_dim,
                        x.dtype)
    return proj, KVCache(new_k, new_v, idx + 1)


def apply_cross(params: Params, cfg: AttnConfig, x: jax.Array,
                enc_k: jax.Array, enc_v: jax.Array) -> jax.Array:
    """Cross-attention against precomputed encoder K/V (whisper decoder)."""
    b, s, _ = x.shape
    q = dense(params["wq"], x).reshape(b, s, cfg.n_heads, cfg.head_dim)
    cfg_nc = cfg._replace(causal=False, window=0)
    out = _dense_attention(cfg_nc, q, enc_k, enc_v)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return dense(params["wo"], out)


def project_kv(params: Params, cfg: AttnConfig, enc: jax.Array):
    """Precompute cross-attention K/V from encoder output."""
    b, s, _ = enc.shape
    k = dense(params["wk"], enc).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = dense(params["wv"], enc).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return k, v
