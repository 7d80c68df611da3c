"""Transformer building blocks (pure JAX; GSPMD-shardable).

All attention paths are *chunked* (online-softmax / flash-style in lax) so
that no O(S^2) score tensor is ever materialized -- mandatory for the 32k
prefill and 4k x 256 train shapes to pass the dry-run memory analysis.  The
Pallas kernel in ``repro.kernels.flash_attention`` implements the same math
for TPU; ``attn_impl`` selects the path.

Sharding is expressed through logical constraints (``distributed.shard``):
  batch  -> ("pod","data")    activations' leading batch dim
  heads  -> "model"           when n_heads % tp == 0 (TP attention)
  seq    -> "model"           otherwise (sequence/context parallelism)
  ff/kv  -> "model"           MLP hidden, KV-cache heads
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..distributed.sharding import axis_size, current_mesh_axes, shard
from .config import ModelConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# initializers / norms
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def norm_init(d: int, cfg: ModelConfig, bias: bool = False) -> Params:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layer" or bias:
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    # statistics accumulate in f32 WITHOUT materializing convert(x): a full
    # f32 copy of x gets loop-hoisted by XLA across the layer scan, i.e. an
    # f32 replica of every saved carry (measured: +10 GiB/chip on qwen3).
    d = x.shape[-1]
    if cfg.norm == "layer":
        mu = (jnp.sum(x, axis=-1, keepdims=True, dtype=jnp.float32) / d)
        xc = x - mu.astype(x.dtype)
    else:
        xc = x
    var = jnp.sum(jnp.square(xc), axis=-1, keepdims=True,
                  dtype=jnp.float32) / d
    nf = lax.rsqrt(var + cfg.norm_eps)
    y = xc * nf.astype(x.dtype) * p["scale"].astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def rms_norm_head(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Per-head RMS norm over the last dim (qwen3 qk_norm)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (full / partial fraction / 2d-half)
# ---------------------------------------------------------------------------


def rope_rotate(x: jax.Array, positions: jax.Array, theta: float,
                fraction: float = 1.0) -> jax.Array:
    """Apply RoPE to the first ``fraction`` of the head dim.

    x: (..., S, H, hd); positions: broadcastable to (..., S).
    """
    hd = x.shape[-1]
    rot = int(hd * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    # positions: (B, S) -> angles (B, S, 1, half), broadcast over heads
    ang = positions.astype(jnp.float32)[..., :, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    out = jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass.astype(x.dtype)], axis=-1)
    return out


# ---------------------------------------------------------------------------
# chunked (flash-style) attention -- XLA path
# ---------------------------------------------------------------------------


def _pick_chunk(s: int, target: int) -> int:
    c = min(s, target)
    while s % c:
        c -= 1
    return c


def flash_attention_xla(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool, window: int = 0, q_offset=0,
                        q_chunk: int = 512, kv_chunk: int = 1024,
                        kv_valid: Optional[jax.Array] = None,
                        unroll: bool = False) -> jax.Array:
    """Online-softmax attention without materializing (S, S) scores.

    q: (B, Sq, H, hd); k, v: (B, Skv, KVH, hd_[v]).  GQA via head grouping.
    ``q_offset``: global position of q[0] (decode / sequence-sharding).
    ``window`` > 0: sliding-window attention (keys in [pos-window+1, pos]).
    ``kv_valid``: optional number of valid kv positions (decode caches).
    Returns (B, Sq, H, hd_v).
    """
    B, Sq, H, Dq = q.shape
    _, Skv, KVH, _ = k.shape
    Dv = v.shape[-1]
    G = H // KVH
    qc = _pick_chunk(Sq, q_chunk)
    kc = _pick_chunk(Skv, kv_chunk)
    nq, nk = Sq // qc, Skv // kc
    scale = 1.0 / math.sqrt(Dq)

    # (B, nq, qc, KVH, G, Dq)
    qr = q.reshape(B, nq, qc, KVH, G, Dq)
    kr = k.reshape(B, nk, kc, KVH, Dq)
    vr = v.reshape(B, nk, kc, KVH, Dv)

    def q_block(carry, qi):
        qb = qr[:, qi]  # (B, qc, KVH, G, Dq)
        q_pos = q_offset + qi * qc + jnp.arange(qc)

        def kv_block(state, ki_valid):
            ki, chunk_valid = ki_valid
            m, l, acc = state
            kb = kr[:, ki]      # (B, kc, KVH, Dq)
            vb = vr[:, ki]      # (B, kc, KVH, Dv)
            k_pos = ki * kc + jnp.arange(kc)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.broadcast_to(chunk_valid, (qc, kc))
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            if kv_valid is not None:
                mask &= (k_pos[None, :] < kv_valid)
            s = jnp.where(mask, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard fully-masked rows
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
            corr = jnp.where(jnp.isfinite(m), corr, 0.0)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KVH, G, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, qc), jnp.float32)
        a0 = jnp.zeros((B, KVH, G, qc, Dv), jnp.float32)
        if window and nk > 1:
            # SWA: only kv chunks overlapping [q_start - window, q_end] are
            # visited -> cost O(S*W) not O(S^2). Out-of-range iterations are
            # clipped to a real chunk index but masked out via chunk_valid.
            lo = jnp.maximum((q_offset + qi * qc - window) // kc, 0)
            n_iter = min(nk, (window + qc + kc - 1) // kc + 1)
            js = lo + jnp.arange(n_iter)
            valid = js < nk
            js = jnp.clip(js, 0, nk - 1)
            (m, l, acc), _ = lax.scan(kv_block, (m0, l0, a0), (js, valid),
                                      unroll=unroll)
        else:
            (m, l, acc), _ = lax.scan(
                kv_block, (m0, l0, a0),
                (jnp.arange(nk), jnp.ones((nk,), bool)), unroll=unroll)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return carry, out.astype(q.dtype)

    # rematerialize each q-block in backward: without this the kv scans'
    # per-step probability matrices (nq*nk blocks of f32[qc,kc] per head)
    # are all saved -- the flash-attention backward trick, in lax
    _, outs = lax.scan(jax.checkpoint(q_block), None, jnp.arange(nq),
                       unroll=unroll)
    # outs: (nq, B, KVH, G, qc, Dv) -> (B, Sq, H, Dv)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq, KVH, G, qc, Dv)
    out = jnp.einsum("bnhgqd->bnqhgd", out).reshape(B, Sq, H, Dv)
    return out


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cur_len: jax.Array, *, window: int = 0,
                     kv_chunk: int = 2048, unroll: bool = False) -> jax.Array:
    """Single-token attention over a (possibly ring) KV cache.

    q: (B, 1, H, Dq); caches: (B, S, KVH, D*); cur_len: scalar count of
    valid entries (ring caches pass W once full).  Chunked online-softmax
    over the sequence: never materializes (B, H, S) f32 scores (measured
    +16 GiB/chip on the 34B decode_32k cell unchunked).
    """
    B, _, H, Dq = q.shape
    _, S, KVH, Dv = v_cache.shape
    G = H // KVH
    scale = 1.0 / math.sqrt(Dq)
    qr = q.reshape(B, KVH, G, Dq)
    kc = _pick_chunk(S, kv_chunk)
    nk = S // kc
    kr = k_cache.reshape(B, nk, kc, KVH, Dq)
    vr = v_cache.reshape(B, nk, kc, KVH, Dv)

    def kv_block(state, ki):
        m, l, acc = state
        kb = kr[:, ki]
        vb = vr[:, ki]
        s = jnp.einsum("bhgd,bkhd->bhgk", qr, kb,
                       preferred_element_type=jnp.float32) * scale
        valid = (ki * kc + jnp.arange(kc)) < cur_len
        s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(valid[None, None, None, :],
                      jnp.exp(s - m_safe[..., None]), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgk,bkhd->bhgd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KVH, G), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KVH, G), jnp.float32)
    a0 = jnp.zeros((B, KVH, G, Dv), jnp.float32)
    if nk == 1:
        (m, l, acc), _ = kv_block((m0, l0, a0), jnp.int32(0))
    else:
        (m, l, acc), _ = lax.scan(kv_block, (m0, l0, a0), jnp.arange(nk),
                                  unroll=unroll)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, 1, H, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer (init / train / decode)
# ---------------------------------------------------------------------------


def attn_init(key, cfg: ModelConfig) -> Params:
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.param_dtype)
    p: Params = {
        "wq": dense_init(ks[0], d, cfg.n_heads * hd, dt),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd, dt),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd, dt),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _tp_heads(cfg: ModelConfig) -> bool:
    """Shard attention by heads when divisible by the tp extent; otherwise
    fall back to sequence sharding (llava 56H, hymba 25H)."""
    tp = axis_size("tp")
    return tp > 1 and cfg.n_heads % tp == 0


def _shard_qkv(cfg: ModelConfig, q, k, v):
    """Pick an attention sharding that divides cleanly.

    * heads divisible by tp and kv-heads divisible -> classic TP attention;
    * heads divisible but kv-heads NOT (qwen3 kv=8, chatglm kv=2 on tp=16):
      broadcast KV to full heads first -- otherwise the (KVH, G) split inside
      flash attention has no shardable axis and GSPMD replicates the whole
      score computation (measured: 132 GiB/chip on qwen3 train_4k);
    * heads not divisible (llava 56H, hymba 25H) -> sequence sharding.
    """
    tp = axis_size("tp")
    kvh = k.shape[2]
    if _tp_heads(cfg):
        q = shard(q, "batch", None, "tp", None)
        if kvh % tp != 0 and q.shape[2] % kvh == 0:
            g = q.shape[2] // kvh
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        k = shard(k, "batch", None, "tp", None)
        v = shard(v, "batch", None, "tp", None)
    else:     # sequence sharding over the model axis
        q = shard(q, "batch", "seq", None, None)
        k = shard(k, "batch", None, None, None)
        v = shard(v, "batch", None, None, None)
    return q, k, v


def qkv_project(p: Params, cfg: ModelConfig, x: jax.Array,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    hd = cfg.hd
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm_head(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm_head(k, p["k_norm"], cfg.norm_eps)
    q = rope_rotate(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = rope_rotate(k, positions, cfg.rope_theta, cfg.rope_fraction)
    return q, k, v


def attn_apply(p: Params, cfg: ModelConfig, x: jax.Array,
               positions: jax.Array) -> jax.Array:
    """Full-sequence (train / prefill) attention."""
    B, S, _ = x.shape
    q, k, v = qkv_project(p, cfg, x, positions)
    q, k, v = _shard_qkv(cfg, q, k, v)
    if cfg.attn_impl == "pallas_interpret":
        from ..kernels.flash_attention.ops import flash_attention as fa
        out = fa(q, k, v, causal=cfg.causal, window=cfg.sliding_window,
                 interpret=True)
    else:
        out = flash_attention_xla(q, k, v, causal=cfg.causal,
                                  window=cfg.sliding_window,
                                  q_chunk=cfg.attn_q_chunk,
                                  kv_chunk=cfg.attn_kv_chunk,
                                  unroll=cfg.unroll_scans)
    out = out.reshape(B, S, cfg.n_heads * cfg.hd)
    return shard(out @ p["wo"].astype(x.dtype), "batch", None, None)


def attn_decode(p: Params, cfg: ModelConfig, x: jax.Array, cache: Dict,
                pos: jax.Array) -> Tuple[jax.Array, Dict]:
    """One-token decode with KV cache (ring buffer when SWA)."""
    B = x.shape[0]
    q, k, v = qkv_project(p, cfg, x, pos[:, None])
    W = cache["k"].shape[1]
    slot = (pos[0] % W) if cfg.sliding_window else pos[0]
    k_cache = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
    v_cache = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
    cur = jnp.minimum(pos[0] + 1, W)
    out = decode_attention(q, k_cache, v_cache, cur,
                           kv_chunk=cfg.decode_kv_chunk,
                           unroll=cfg.unroll_scans)
    out = out.reshape(B, 1, cfg.n_heads * cfg.hd)
    y = out @ p["wo"].astype(x.dtype)
    return y, {"k": k_cache, "v": v_cache}


def kv_cache_init(cfg: ModelConfig, batch: int, seq: int, dtype) -> Dict:
    W = min(seq, cfg.sliding_window) if cfg.sliding_window else seq
    shape = (batch, W, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_init(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    H, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "w_q": dense_init(ks[0], d, H * (dn + dr), dt),
        "w_dkv": dense_init(ks[1], d, r + dr, dt),       # latent + shared rope key
        "w_uk": dense_init(ks[2], r, H * dn, dt),        # latent -> k_nope
        "w_uv": dense_init(ks[3], r, H * dv, dt),        # latent -> v
        "kv_norm": jnp.ones((r,), jnp.float32),
        "wo": dense_init(ks[4], H * dv, d, dt),
    }


def _mla_qc(p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    B, S, _ = x.shape
    H, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    r = cfg.kv_lora_rank
    q = (x @ p["w_q"].astype(x.dtype)).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope_rotate(q_rope, positions, cfg.rope_theta)
    ckv = x @ p["w_dkv"].astype(x.dtype)
    c, k_rope = ckv[..., :r], ckv[..., r:]
    c = rms_norm_head(c, p["kv_norm"], cfg.norm_eps)
    k_rope = rope_rotate(k_rope[:, :, None, :], positions, cfg.rope_theta)
    return q_nope, q_rope, c, k_rope[:, :, 0, :]


def mla_apply(p: Params, cfg: ModelConfig, x: jax.Array,
              positions: jax.Array) -> jax.Array:
    """Training/prefill MLA: expand latent to per-head K/V, flash attention."""
    B, S, _ = x.shape
    H, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_rope, c, k_rope = _mla_qc(p, cfg, x, positions)
    k_nope = (c @ p["w_uk"].astype(x.dtype)).reshape(B, S, H, dn)
    v = (c @ p["w_uv"].astype(x.dtype)).reshape(B, S, H, dv)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))], axis=-1)
    q = shard(q, "batch", None, "tp", None)
    k = shard(k, "batch", None, "tp", None)
    v = shard(v, "batch", None, "tp", None)
    out = flash_attention_xla(q, k, v, causal=cfg.causal,
                              q_chunk=cfg.attn_q_chunk,
                              kv_chunk=cfg.attn_kv_chunk,
                              unroll=cfg.unroll_scans)
    out = out.reshape(B, S, H * dv)
    return shard(out @ p["wo"].astype(x.dtype), "batch", None, None)


def mla_decode(p: Params, cfg: ModelConfig, x: jax.Array, cache: Dict,
               pos: jax.Array) -> Tuple[jax.Array, Dict]:
    """Absorbed-matmul latent decode: the cache stores (c, k_rope) only --
    (kv_lora + rope_dim) floats/token instead of 2*H*hd (paper's MLA win)."""
    B = x.shape[0]
    H, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    q_nope, q_rope, c_new, kr_new = _mla_qc(p, cfg, x, pos[:, None])
    S = cache["c"].shape[1]
    c_cache = lax.dynamic_update_slice_in_dim(
        cache["c"], c_new.astype(cache["c"].dtype), pos[0], axis=1)
    kr_cache = lax.dynamic_update_slice_in_dim(
        cache["kr"], kr_new.astype(cache["kr"].dtype), pos[0], axis=1)
    w_uk = p["w_uk"].astype(x.dtype).reshape(r, H, dn)
    # absorb: q_lat[b,h,r] = q_nope[b,h,dn] . w_uk[r,h,dn]
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
    s = jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32),
                   c_cache.astype(jnp.float32))
    s += jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32),
                    kr_cache.astype(jnp.float32))
    s *= 1.0 / math.sqrt(dn + dr)
    valid = jnp.arange(S)[None, None, :] <= pos[0]
    s = jnp.where(valid, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", pr, c_cache.astype(jnp.float32))
    w_uv = p["w_uv"].astype(x.dtype).reshape(r, H, dv)
    out = jnp.einsum("bhr,rhd->bhd", o_lat.astype(x.dtype), w_uv)
    y = out.reshape(B, 1, H * dv) @ p["wo"].astype(x.dtype)
    return y, {"c": c_cache, "kr": kr_cache}


def mla_cache_init(cfg: ModelConfig, batch: int, seq: int, dtype) -> Dict:
    return {"c": jnp.zeros((batch, seq, cfg.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, seq, cfg.qk_rope_dim), dtype)}


# ---------------------------------------------------------------------------
# MLP (gated SwiGLU / plain GELU)
# ---------------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    p = {"w_up": dense_init(ks[1], d, ff, dt),
         "w_down": dense_init(ks[2], ff, d, dt)}
    if cfg.mlp_gated:
        p["w_gate"] = dense_init(ks[0], d, ff, dt)
    return p


def mlp_apply(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    up = shard(x @ p["w_up"].astype(x.dtype), "batch", None, "tp")
    if cfg.mlp_gated:
        gate = shard(x @ p["w_gate"].astype(x.dtype), "batch", None, "tp")
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    return shard(h @ p["w_down"].astype(x.dtype), "batch", None, None)


# ---------------------------------------------------------------------------
# Mixture of Experts (fine-grained, shared + routed, top-k)
# ---------------------------------------------------------------------------


def moe_init(key, cfg: ModelConfig) -> Params:
    d, fe = cfg.d_model, cfg.d_ff_expert
    E = cfg.n_routed_experts
    ks = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.param_dtype)
    scale = 1.0 / math.sqrt(d)

    def experts(k, din, dout):
        return (jax.random.normal(k, (E, din, dout), jnp.float32)
                * (1.0 / math.sqrt(din))).astype(dt)

    p: Params = {
        "router": (jax.random.normal(ks[0], (d, E), jnp.float32) * scale
                   ).astype(jnp.float32),
        "w_gate_e": experts(ks[1], d, fe),
        "w_up_e": experts(ks[2], d, fe),
        "w_down_e": experts(ks[3], fe, d),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=fe * cfg.n_shared_experts)
    return p


def _route(p: Params, cfg: ModelConfig, x_flat: jax.Array):
    """Top-k routing with normalized weights + aux load-balance loss."""
    logits = (x_flat.astype(jnp.float32) @ p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, cfg.moe_top_k)          # (T, k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    E = cfg.n_routed_experts
    # aux: E * sum_e f_e * P_e  (Switch-style)
    f = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1), axis=0)
    pm = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * pm) * cfg.router_aux_coef
    return w.astype(x_flat.dtype), idx, aux


def _expert_ffn(recv: jax.Array, wg, wu, wd, dtype) -> jax.Array:
    """(E_loc, C, d) -> (E_loc, C, d) batched expert matmuls (MXU-friendly)."""
    g = jnp.einsum("ecd,edf->ecf", recv, wg.astype(dtype))
    u = jnp.einsum("ecd,edf->ecf", recv, wu.astype(dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, wd.astype(dtype))


def _dispatch_combine(p: Params, cfg: ModelConfig, x_flat: jax.Array,
                      ep: int, axis_name: Optional[str]) -> Tuple[jax.Array, jax.Array]:
    """Capacity-based dispatch -> (all_to_all) -> expert FFN -> combine.

    Runs inside shard_map when ``axis_name`` is set (EP over the model axis),
    or locally (ep=1) for smoke tests and the decode path.
    """
    T, d = x_flat.shape
    E, k = cfg.n_routed_experts, cfg.moe_top_k
    C = max(1, int(math.ceil(T * k / E * cfg.moe_capacity_factor)))
    w, idx, aux = _route(p, cfg, x_flat)

    flat_e = idx.reshape(-1)                                   # (T*k,)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)            # (T*k, E)
    # position of each (token, k) within its expert's capacity buffer
    pos_in_e = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1,
                                   flat_e[:, None], axis=1)[:, 0]
    keep = pos_in_e < C
    slot = jnp.where(keep, flat_e * C + pos_in_e, E * C)       # overflow row
    x_rep = jnp.repeat(x_flat, k, axis=0)                      # (T*k, d)
    send = jnp.zeros((E * C + 1, d), x_flat.dtype).at[slot].set(x_rep)
    send = send[:-1].reshape(E, C, d)

    if axis_name is not None and ep > 1:
        recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=1,
                              tiled=True)                      # (E/ep, C*ep, d)
    else:
        recv = send
    out = _expert_ffn(recv, p["w_gate_e"], p["w_up_e"], p["w_down_e"],
                      x_flat.dtype)
    if axis_name is not None and ep > 1:
        out = lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0,
                             tiled=True)                       # (E, C, d)
    got = jnp.concatenate([out.reshape(E * C, d),
                           jnp.zeros((1, d), x_flat.dtype)], axis=0)
    y = got[slot] * keep[:, None].astype(x_flat.dtype)         # (T*k, d)
    y = (y.reshape(T, k, d) * w[..., None]).sum(axis=1)
    return y, aux


def moe_apply(p: Params, cfg: ModelConfig, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """Routed + shared experts.  Train/prefill uses shard_map EP over the
    model axis; without a mesh it degrades to local dispatch (same math)."""
    B, S, d = x.shape
    axes = current_mesh_axes()
    ep = axis_size("tp")
    use_ep = ("model" in axes) and ep > 1 and S % ep == 0
    if use_ep:
        from ..distributed.sharding import current_mesh
        from jax.sharding import PartitionSpec as P
        mesh = current_mesh()
        dp_axes = tuple(a for a in ("pod", "data") if a in axes)

        def blk(xb, router, wg, wu, wd):
            pb = {"router": router, "w_gate_e": wg, "w_up_e": wu, "w_down_e": wd}
            t = xb.reshape(-1, d)
            y, aux = _dispatch_combine(pb, cfg, t, ep, "model")
            aux = lax.pmean(aux, axis_name="model")
            if dp_axes:
                aux = lax.pmean(aux, axis_name=dp_axes)
            return y.reshape(xb.shape), aux

        y, aux = jax.shard_map(
            blk, mesh=mesh,
            in_specs=(P(dp_axes or None, "model", None), P(None, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=(P(dp_axes or None, "model", None), P()),
        )(x, p["router"], p["w_gate_e"], p["w_up_e"], p["w_down_e"])
    else:
        y, aux = _dispatch_combine(p, cfg, x.reshape(-1, d), 1, None)
        y = y.reshape(B, S, d)
    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], cfg, x)
    return shard(y, "batch", None, None), aux
