"""Decoder-LM assembly covering the dense / MoE / SSM / hybrid families.

One ``ModelConfig`` describes every assigned architecture; ``init`` builds a
stacked-parameter pytree and ``apply_train`` / ``apply_prefill`` /
``apply_decode`` run it with ``lax.scan`` over layers (O(1) compile cost in
depth — essential for the 80-layer dry-run cells).

Per-layer heterogeneity that is *data* (sliding-window size alternation in
gemma2) rides through the scan as a per-layer array; heterogeneity that is
*structural* (zamba2's periodic shared attention) is handled by
:mod:`repro.models.hybrid`.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding.context import shard_logits, shard_seq

from . import attention, layers, moe as moe_lib, scan_util, ssm as ssm_lib
from .attention import AttnConfig, KVCache
from .layers import Axes, Params
from .moe import MoEConfig
from .ssm import SSMCache, SSMConfig


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | encdec
    n_layers: int
    d_model: int
    vocab: int
    # attention (unused for pure-ssm archs)
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    activation: str = "silu"
    rope_theta: float = 10000.0
    final_softcap: float = 0.0
    attn_softcap: float = 0.0
    window_pattern: Tuple[int, ...] = ()   # cycled per layer; 0 = global
    post_norms: bool = False
    norm_plus_one: bool = False
    embed_scale: bool = False
    tied_embeddings: bool = True
    # moe
    moe: Optional[MoEConfig] = None
    dense_residual: bool = False
    # ssm / hybrid
    ssm: Optional[SSMConfig] = None
    attn_every: int = 0
    shared_attn: bool = False
    shared_window: int = 0
    # encdec
    encoder_layers: int = 0
    encoder_seq: int = 0
    # stubs
    vision_tokens: int = 0
    max_seq: int = 131072
    # activation rematerialization policy for the training path:
    # none | dots | full  (launch/train selects per shape cell)
    remat: str = "none"

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a multiple of 128 when not divisible by 16:
        an unshardable LM head replicates (B,S,V) fp32 logits across the
        model axis — 12.3 GiB/device for mamba2's 50280 vocab (§Perf-3).
        Lookups never touch the pad rows; _logits masks the pad columns."""
        if self.vocab % 16 == 0:
            return self.vocab
        return ((self.vocab + 127) // 128) * 128

    @property
    def attn_cfg(self) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            rope_theta=self.rope_theta, logit_softcap=self.attn_softcap,
        )

    def layer_windows(self) -> jnp.ndarray:
        if not self.window_pattern:
            return jnp.zeros((self.n_layers,), jnp.int32)
        pat = list(self.window_pattern)
        reps = (self.n_layers + len(pat) - 1) // len(pat)
        return jnp.asarray((pat * reps)[: self.n_layers], jnp.int32)

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks), for roofline
        MODEL_FLOPS = 6·N·D."""
        d = self.d_model
        n = 0
        n += self.vocab * d * (1 if self.tied_embeddings else 2)
        L = self.n_layers
        if self.family in ("dense", "moe", "encdec", "vlm"):
            attn = d * (self.n_heads + 2 * self.n_kv_heads) * self.head_dim \
                + self.n_heads * self.head_dim * d
            n += L * attn
        if self.family in ("dense", "encdec", "vlm"):
            gates = 3 if self.activation_is_glu else 2
            n += L * gates * d * self.d_ff
        if self.moe is not None:
            n += L * (d * self.moe.n_experts
                      + 3 * self.moe.n_experts * d * self.moe.d_ff)
            if self.dense_residual:
                n += L * 3 * d * self.d_ff
        if self.ssm is not None:
            s = self.ssm
            proj = 2 * s.d_inner + 2 * s.n_groups * s.d_state + s.n_heads
            ssm_l = d * proj + s.d_inner * d
            n_ssm_layers = L
            if self.family == "hybrid" and self.attn_every:
                pass  # all L layers are ssm; shared attn counted once below
            n += n_ssm_layers * ssm_l
        if self.shared_attn:
            n += d * (self.n_heads + 2 * self.n_kv_heads) * self.head_dim \
                + self.n_heads * self.head_dim * d + 3 * d * self.d_ff
        if self.encoder_layers:
            attn = d * (self.n_heads + 2 * self.n_kv_heads) * self.head_dim \
                + self.n_heads * self.head_dim * d
            n += self.encoder_layers * (attn + 2 * d * self.d_ff)
            # decoder cross-attention
            n += L * attn
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: top-k experts only)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        n = self.param_count()
        n -= self.n_layers * 3 * self.moe.n_experts * d * self.moe.d_ff
        n += self.n_layers * 3 * self.moe.top_k * d * self.moe.d_ff
        return n

    @property
    def activation_is_glu(self) -> bool:
        return self.activation in ("silu", "gelu_glu")


# ------------------------------------------------------------------ init ---

def _init_block(key: jax.Array, cfg: ModelConfig, dtype) -> Tuple[Params, Axes]:
    """One decoder block (attention or ssm family)."""
    p: Params = {}
    a: Axes = {}
    ks = jax.random.split(key, 8)
    if cfg.family == "ssm":
        p["pre_norm"], a["pre_norm"] = layers.rmsnorm_init(cfg.d_model, dtype)
        p["mixer"], a["mixer"] = ssm_lib.init(ks[0], cfg.ssm, dtype)
        return p, a
    p["pre_attn_norm"], a["pre_attn_norm"] = layers.rmsnorm_init(
        cfg.d_model, dtype)
    p["attn"], a["attn"] = attention.init(ks[0], cfg.attn_cfg, dtype)
    p["pre_mlp_norm"], a["pre_mlp_norm"] = layers.rmsnorm_init(
        cfg.d_model, dtype)
    if cfg.post_norms:
        p["post_attn_norm"], a["post_attn_norm"] = layers.rmsnorm_init(
            cfg.d_model, dtype)
        p["post_mlp_norm"], a["post_mlp_norm"] = layers.rmsnorm_init(
            cfg.d_model, dtype)
    if cfg.moe is not None:
        p["moe"], a["moe"] = moe_lib.init(ks[1], cfg.moe, dtype)
        if cfg.dense_residual:
            p["mlp"], a["mlp"] = layers.glu_mlp_init(
                ks[2], cfg.d_model, cfg.d_ff, dtype)
    elif cfg.activation_is_glu:
        p["mlp"], a["mlp"] = layers.glu_mlp_init(
            ks[2], cfg.d_model, cfg.d_ff, dtype)
    else:
        p["mlp"], a["mlp"] = layers.mlp_init(
            ks[2], cfg.d_model, cfg.d_ff, dtype)
    return p, a


def init(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32
         ) -> Tuple[Params, Axes]:
    keys = jax.random.split(key, cfg.n_layers + 3)
    p: Params = {}
    a: Axes = {}
    p["embed"], a["embed"] = layers.embed_init(
        keys[0], cfg.padded_vocab, cfg.d_model, dtype)
    captured = {}

    def block(k):
        bp, captured["axes"] = _init_block(k, cfg, dtype)
        return bp

    # One traced block vmapped over the layer keys: bit-identical to
    # initializing layer by layer and stacking, but the program does not
    # grow with depth (compiling a 32-layer init for v5e took 71 s) and
    # holds no second copy of the stack.
    p["blocks"] = jax.vmap(block)(keys[1:1 + cfg.n_layers])
    a["blocks"] = layers.stacked_axes(captured["axes"])
    p["final_norm"], a["final_norm"] = layers.rmsnorm_init(cfg.d_model, dtype)
    if not cfg.tied_embeddings:
        p["lm_head"], a["lm_head"] = layers.dense_init(
            keys[-1], cfg.d_model, cfg.padded_vocab, ("embed", "vocab"),
            dtype)
    return p, a


# --------------------------------------------------------------- forward ---

def _block_apply_train(cfg: ModelConfig, bp: Params, x: jax.Array,
                       window: jax.Array, rope) -> Tuple[jax.Array, jax.Array]:
    """One block, training path. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        h = layers.rmsnorm(bp["pre_norm"], x, plus_one=cfg.norm_plus_one)
        x = x + ssm_lib.apply_train(bp["mixer"], cfg.ssm, h)
        return shard_seq(x), aux
    h = layers.rmsnorm(bp["pre_attn_norm"], x, plus_one=cfg.norm_plus_one)
    acfg = cfg.attn_cfg._replace(window=int(window))
    attn_out = attention.apply_train(bp["attn"], acfg, h, rope=rope)
    if cfg.post_norms:
        attn_out = layers.rmsnorm(bp["post_attn_norm"], attn_out,
                                  plus_one=cfg.norm_plus_one)
    x = x + attn_out
    h = layers.rmsnorm(bp["pre_mlp_norm"], x, plus_one=cfg.norm_plus_one)
    if cfg.moe is not None:
        mo, a = moe_lib.apply(bp["moe"], cfg.moe, h)
        aux = aux + a
        if cfg.dense_residual:
            mo = mo + layers.glu_mlp(bp["mlp"], h, cfg.activation)
        mlp_out = mo
    elif cfg.activation_is_glu:
        act = "silu" if cfg.activation == "silu" else "gelu"
        mlp_out = layers.glu_mlp(bp["mlp"], h, act)
    else:
        mlp_out = layers.mlp(bp["mlp"], h)
    if cfg.post_norms:
        mlp_out = layers.rmsnorm(bp["post_mlp_norm"], mlp_out,
                                 plus_one=cfg.norm_plus_one)
    return shard_seq(x + mlp_out), aux


def _maybe_remat(body, remat: str):
    """Per-layer activation checkpointing around the scan body."""
    if remat == "none":
        return body
    if remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(body, policy=policy, prevent_cse=False)


def _rope_tables(cfg: ModelConfig, max_pos: int):
    if cfg.family == "ssm":
        return None
    return layers.rope_frequencies(cfg.head_dim, max_pos, cfg.rope_theta)


def _logits(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    x = layers.rmsnorm(params["final_norm"], x, plus_one=cfg.norm_plus_one)
    if cfg.tied_embeddings:
        w = params["embed"]["w"].astype(x.dtype)
        logits = x @ w.T
    else:
        logits = layers.dense(params["lm_head"], x)
    logits = shard_logits(logits)
    logits = layers.softcap(logits.astype(jnp.float32), cfg.final_softcap)
    if cfg.padded_vocab != cfg.vocab:
        # pad columns carry no probability mass
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad_mask[None, None, :], -1e30, logits)
    return logits


def apply_train(params: Params, cfg: ModelConfig, tokens: jax.Array,
                prefix_embeds: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """tokens (B, S) → (logits (B, S', vocab) fp32, aux_loss).

    ``prefix_embeds`` (B, P, d) — VLM stub frontend: precomputed patch
    embeddings prepended to the token embeddings.
    """
    x = layers.embed(params["embed"], tokens)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    x = shard_seq(x)
    s = x.shape[1]
    rope = _rope_tables(cfg, s)
    windows = cfg.layer_windows()

    # Window sizes vary per layer (gemma2): the scan carries them as data,
    # but attention masks need static window values → group layers by
    # distinct window, scanning each homogeneous group.
    distinct = tuple(dict.fromkeys(cfg.window_pattern)) or (0,)
    if len(distinct) == 1:
        def body(carry, bp):
            x, aux = carry
            x, a = _block_apply_train(cfg, bp, x, int(distinct[0]), rope)
            return (x, aux + a), None
        body = _maybe_remat(body, cfg.remat)
        (x, aux), _ = scan_util.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["blocks"])
    else:
        # Alternating pattern: scan over layer *pairs* (gemma2: local,global)
        pat = cfg.window_pattern
        npat = len(pat)
        assert cfg.n_layers % npat == 0, (cfg.n_layers, pat)
        grouped = jax.tree.map(
            lambda a: a.reshape((cfg.n_layers // npat, npat) + a.shape[1:]),
            params["blocks"])

        def body(carry, bps):
            x, aux = carry
            for j, w in enumerate(pat):
                bp = jax.tree.map(lambda a: a[j], bps)
                x, a = _block_apply_train(cfg, bp, x, int(w), rope)
                aux = aux + a
            return (x, aux), None
        body = _maybe_remat(body, cfg.remat)
        (x, aux), _ = scan_util.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   grouped)
    logits = _logits(cfg, params, x)
    return logits, aux


# ------------------------------------------------------------- serving ---

class LayerCaches(NamedTuple):
    """Stacked per-layer caches (leading axis = layer)."""
    kv: Optional[KVCache]
    ssm: Optional[SSMCache]


def init_caches(cfg: ModelConfig, batch: int, max_s: int,
                dtype=jnp.bfloat16) -> LayerCaches:
    L = cfg.n_layers
    kv = ssm = None
    if cfg.family in ("dense", "moe", "encdec", "vlm"):
        one = attention.init_cache(cfg.attn_cfg, batch, max_s, dtype)
        kv = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape).copy(), one)
    if cfg.family == "ssm":
        one = ssm_lib.init_cache(cfg.ssm, batch, dtype)
        ssm = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape).copy(), one)
    return LayerCaches(kv=kv, ssm=ssm)


def _block_apply_decode(cfg: ModelConfig, bp: Params, x, window, rope,
                        kv: Optional[KVCache], sc: Optional[SSMCache]):
    if cfg.family == "ssm":
        h = layers.rmsnorm(bp["pre_norm"], x, plus_one=cfg.norm_plus_one)
        out, sc = ssm_lib.apply_decode(bp["mixer"], cfg.ssm, h, sc)
        return x + out, kv, sc
    h = layers.rmsnorm(bp["pre_attn_norm"], x, plus_one=cfg.norm_plus_one)
    acfg = cfg.attn_cfg._replace(window=int(window))
    attn_out, kv = attention.apply_decode(bp["attn"], acfg, h, kv, rope=rope)
    if cfg.post_norms:
        attn_out = layers.rmsnorm(bp["post_attn_norm"], attn_out,
                                  plus_one=cfg.norm_plus_one)
    x = x + attn_out
    h = layers.rmsnorm(bp["pre_mlp_norm"], x, plus_one=cfg.norm_plus_one)
    if cfg.moe is not None:
        mo, _ = moe_lib.apply(bp["moe"], cfg.moe, h)
        if cfg.dense_residual:
            mo = mo + layers.glu_mlp(bp["mlp"], h, cfg.activation)
        mlp_out = mo
    elif cfg.activation_is_glu:
        act = "silu" if cfg.activation == "silu" else "gelu"
        mlp_out = layers.glu_mlp(bp["mlp"], h, act)
    else:
        mlp_out = layers.mlp(bp["mlp"], h)
    if cfg.post_norms:
        mlp_out = layers.rmsnorm(bp["post_mlp_norm"], mlp_out,
                                 plus_one=cfg.norm_plus_one)
    return x + mlp_out, kv, sc


def apply_decode(params: Params, cfg: ModelConfig, tokens: jax.Array,
                 caches: LayerCaches) -> Tuple[jax.Array, LayerCaches]:
    """One-token decode: tokens (B, 1) → (logits (B, 1, V), new caches)."""
    x = layers.embed(params["embed"], tokens)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    max_s = caches.kv.k.shape[2] if caches.kv is not None else cfg.max_seq
    rope = _rope_tables(cfg, max_s)
    windows = cfg.layer_windows()

    def body(x, scanned):
        bp, w, kv, sc = scanned
        # window must be static for masking math; decode mask uses dynamic
        # comparison so traced w is fine here.
        if cfg.family == "ssm":
            xo, _, sc2 = _block_apply_decode(cfg, bp, x, 0, rope, None, sc)
            return xo, (None, sc2)
        acfg = cfg.attn_cfg
        h = layers.rmsnorm(bp["pre_attn_norm"], x,
                           plus_one=cfg.norm_plus_one)
        attn_out, kv2 = _decode_attn_dynwin(bp["attn"], acfg, h, kv, rope, w)
        if cfg.post_norms:
            attn_out = layers.rmsnorm(bp["post_attn_norm"], attn_out,
                                      plus_one=cfg.norm_plus_one)
        x = x + attn_out
        h = layers.rmsnorm(bp["pre_mlp_norm"], x, plus_one=cfg.norm_plus_one)
        if cfg.moe is not None:
            mo, _ = moe_lib.apply(bp["moe"], cfg.moe, h)
            if cfg.dense_residual:
                mo = mo + layers.glu_mlp(bp["mlp"], h, cfg.activation)
            mlp_out = mo
        elif cfg.activation_is_glu:
            act = "silu" if cfg.activation == "silu" else "gelu"
            mlp_out = layers.glu_mlp(bp["mlp"], h, act)
        else:
            mlp_out = layers.mlp(bp["mlp"], h)
        if cfg.post_norms:
            mlp_out = layers.rmsnorm(bp["post_mlp_norm"], mlp_out,
                                     plus_one=cfg.norm_plus_one)
        return x + mlp_out, (kv2, None)

    def scan_body(x, scanned):
        out, new = body(x, scanned)
        return out, new

    scanned = (params["blocks"], windows,
               caches.kv if caches.kv is not None else None,
               caches.ssm if caches.ssm is not None else None)
    # lax.scan needs every scanned leaf to have the layer leading dim; the
    # None entries are passed through a closure instead.
    if cfg.family == "ssm":
        def sbody(x, sl):
            bp, sc = sl
            out, (_, sc2) = body(x, (bp, jnp.int32(0), None, sc))
            return out, sc2
        x, new_ssm = scan_util.scan(sbody, x, (params["blocks"], caches.ssm))
        new_caches = LayerCaches(kv=None, ssm=new_ssm)
    else:
        def abody(x, sl):
            bp, w, kv = sl
            out, (kv2, _) = body(x, (bp, w, kv, None))
            return out, kv2
        x, new_kv = scan_util.scan(
            abody, x, (params["blocks"], windows, caches.kv))
        new_caches = LayerCaches(kv=new_kv, ssm=None)
    logits = _logits(cfg, params, x)
    return logits, new_caches


def _decode_attn_dynwin(p, acfg: AttnConfig, h, kv: KVCache, rope, w):
    """Decode attention with a *traced* per-layer window size (gemma2's
    alternation rides through lax.scan as data)."""
    b = h.shape[0]
    pos = jnp.broadcast_to(kv.length, (b, 1))
    q, k, v = attention._project_qkv(p, acfg, h, pos, rope)
    idx = kv.length
    new_k = jax.lax.dynamic_update_slice(
        kv.k, k.astype(kv.k.dtype), (0, idx, 0, 0))
    new_v = jax.lax.dynamic_update_slice(
        kv.v, v.astype(kv.v.dtype), (0, idx, 0, 0))
    max_s = kv.k.shape[1]
    group = acfg.n_heads // acfg.n_kv_heads
    scale = acfg.query_pre_scale or acfg.head_dim ** -0.5
    # Compute at activation precision: the bf16 cache quantizes k/v
    # storage, but downcasting the fresh q or the softmax probabilities to
    # the cache dtype doubles the quantization error vs the teacher-forced
    # forward pass (the glm4_9b decode-drift bug).
    kq = jnp.repeat(new_k, group, axis=2)
    vq = jnp.repeat(new_v, group, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kq.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    logits = layers.softcap(logits, acfg.logit_softcap)
    kpos = jnp.arange(max_s)
    mask = kpos[None, :] <= idx
    mask &= jnp.where(w > 0, kpos[None, :] > idx - w, True)
    logits = jnp.where(mask[None, None], logits, -1e30)
    pattn = jax.nn.softmax(logits, axis=-1)
    # P·V·Wo association order comes from the serving planner (trace-time
    # consult, amortised by the jit cache — see attention.pv_wo_output).
    proj = attention.pv_wo_output(pattn, vq, p["wo"], acfg.n_heads,
                                  acfg.head_dim, h.dtype)
    return proj, KVCache(new_k, new_v, idx + 1)


def apply_prefill(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  caches: LayerCaches,
                  prefix_embeds: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, LayerCaches]:
    """Prefill: full-sequence forward that also fills the caches."""
    x = layers.embed(params["embed"], tokens)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    rope = _rope_tables(cfg, max(s, cfg.max_seq if caches.kv is None
                                 else caches.kv.k.shape[2]))
    windows = cfg.layer_windows()

    if cfg.family == "ssm":
        def sbody(x, sl):
            bp, sc = sl
            h = layers.rmsnorm(bp["pre_norm"], x, plus_one=cfg.norm_plus_one)
            out, sc2 = ssm_lib.apply_prefill(bp["mixer"], cfg.ssm, h, sc)
            return x + out, sc2
        x, new_ssm = scan_util.scan(sbody, x, (params["blocks"], caches.ssm))
        logits = _logits(cfg, params, x)
        return logits, LayerCaches(kv=None, ssm=new_ssm)

    def make_abody(window: int):
        def abody(x, sl):
            bp, kv = sl
            acfg = cfg.attn_cfg._replace(window=window)
            h = layers.rmsnorm(bp["pre_attn_norm"], x,
                               plus_one=cfg.norm_plus_one)
            attn_out, (k, v) = attention.apply_train(
                bp["attn"], acfg, h, rope=rope, return_kv=True,
                differentiable=False)
            new_kv = KVCache(
                k=jax.lax.dynamic_update_slice(
                    kv.k, k.astype(kv.k.dtype), (0, 0, 0, 0)),
                v=jax.lax.dynamic_update_slice(
                    kv.v, v.astype(kv.v.dtype), (0, 0, 0, 0)),
                length=jnp.asarray(s, jnp.int32),
            )
            if cfg.post_norms:
                attn_out = layers.rmsnorm(bp["post_attn_norm"], attn_out,
                                          plus_one=cfg.norm_plus_one)
            x = x + attn_out
            h = layers.rmsnorm(bp["pre_mlp_norm"], x,
                               plus_one=cfg.norm_plus_one)
            if cfg.moe is not None:
                mo, _ = moe_lib.apply(bp["moe"], cfg.moe, h)
                if cfg.dense_residual:
                    mo = mo + layers.glu_mlp(bp["mlp"], h, cfg.activation)
                mlp_out = mo
            elif cfg.activation_is_glu:
                act = "silu" if cfg.activation == "silu" else "gelu"
                mlp_out = layers.glu_mlp(bp["mlp"], h, act)
            else:
                mlp_out = layers.mlp(bp["mlp"], h)
            if cfg.post_norms:
                mlp_out = layers.rmsnorm(bp["post_mlp_norm"], mlp_out,
                                         plus_one=cfg.norm_plus_one)
            return shard_seq(x + mlp_out), new_kv
        return abody

    distinct = tuple(dict.fromkeys(cfg.window_pattern)) or (0,)
    if len(distinct) == 1:
        x, new_kv = scan_util.scan(make_abody(int(distinct[0])), x,
                                 (params["blocks"], caches.kv))
    else:
        pat = cfg.window_pattern
        npat = len(pat)
        assert cfg.n_layers % npat == 0
        grouped_b = jax.tree.map(
            lambda a: a.reshape((cfg.n_layers // npat, npat) + a.shape[1:]),
            params["blocks"])
        grouped_c = jax.tree.map(
            lambda a: a.reshape((cfg.n_layers // npat, npat) + a.shape[1:]),
            caches.kv)

        def gbody(x, sl):
            bps, kvs = sl
            new = []
            for j, w in enumerate(pat):
                bp = jax.tree.map(lambda a: a[j], bps)
                kv = jax.tree.map(lambda a: a[j], kvs)
                x, kv2 = make_abody(int(w))(x, (bp, kv))
                new.append(kv2)
            return x, jax.tree.map(lambda *ys: jnp.stack(ys), *new)

        x, new_kv_g = scan_util.scan(gbody, x, (grouped_b, grouped_c))
        new_kv = jax.tree.map(
            lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), new_kv_g)
    logits = _logits(cfg, params, x)
    return logits, LayerCaches(kv=new_kv, ssm=None)
