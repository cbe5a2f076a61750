"""Attention: MHA / GQA / MLA, RoPE, windowed attention.

All attention entry points take ``impl``:
  - "xla":   pure-jnp reference path (used on CPU and as the oracle)
  - "flash": Pallas flash-attention kernel (TPU target; interpret-mode on CPU)

Shapes follow [batch, seq, heads, head_dim] ("BSHD").
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import (
    Params,
    linear,
    linear_init,
    rmsnorm,
    rmsnorm_init,
)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0,
                     dtype=jnp.float32) -> jnp.ndarray:
    """[max_seq, head_dim//2] angles."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    return jnp.outer(t, inv).astype(dtype)  # [S, D/2]


def apply_rope(x: jnp.ndarray, angles: jnp.ndarray) -> jnp.ndarray:
    """x: [B, S, H, D]; angles: [S, D/2] (already positioned)."""
    d_half = x.shape[-1] // 2
    x1, x2 = x[..., :d_half], x[..., d_half:]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


# ---------------------------------------------------------------------------
# Core scaled-dot-product attention (reference / XLA path)
# ---------------------------------------------------------------------------

def sdpa_xla(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
             causal: bool = False, bias: jnp.ndarray | None = None,
             q_offset: int = 0, scale: float | None = None) -> jnp.ndarray:
    """q/k: [B,Sq,Hq,D], v: [B,Sk,Hkv,Dv] with Hq % Hkv == 0 (GQA).

    Dv may differ from D (MLA: qk_head_dim != v_head_dim).
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, Dv = v.shape
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    qg = q.reshape(B, Sq, Hkv, group, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(Sq) + q_offset
        kpos = jnp.arange(Sk)
        mask = kpos[None, :] <= qpos[:, None]  # [Sq, Sk]
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, Dv).astype(q.dtype)


CHUNKED_THRESHOLD = 2048  # switch to q-chunked attention above this seq len
# §Perf knob: q-chunk size. Bigger chunks re-read K/V fewer times (bytes
# scale ~ S/chunk) at the cost of a larger transient logits tile.
import os as _os
_CHUNK = int(_os.environ.get("REPRO_ATTN_CHUNK", "1024"))


def sdpa_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                 causal: bool = False, q_offset: int = 0,
                 scale: float | None = None,
                 chunk: int = 1024) -> jnp.ndarray:
    """Exact attention with O(chunk * Sk) logits memory via lax.scan over
    query chunks — the XLA-lowerable stand-in for the Pallas flash kernel
    (same math, bounded VMEM/HBO footprint; on a real TPU the flash
    kernel replaces this path). Sq must be divisible by `chunk` (callers
    route through here only for long, power-of-two sequence cells)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, Dv = v.shape
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if Sq % chunk != 0:
        return sdpa_xla(q, k, v, causal=causal, q_offset=q_offset,
                        scale=scale)
    n_chunks = Sq // chunk
    qc = q.reshape(B, n_chunks, chunk, Hq, D).transpose(1, 0, 2, 3, 4)

    kpos = jnp.arange(Sk)

    def body(carry, xs):
        qi, i = xs
        qg = qi.reshape(B, chunk, Hkv, group, D)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        if causal:
            qpos = i * chunk + jnp.arange(chunk) + q_offset
            mask = kpos[None, :] <= qpos[:, None]
            logits = jnp.where(mask[None, None, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32))
        return carry, o.reshape(B, chunk, Hq, Dv).astype(q.dtype)

    from repro.models.layers import scan_unroll
    _, out = jax.lax.scan(body, None, (qc, jnp.arange(n_chunks)),
                          unroll=scan_unroll())
    return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, Hq, Dv)


def sdpa(q, k, v, *, causal=False, bias=None, q_offset=0, impl="xla",
         scale=None):
    if impl == "flash" and bias is None:
        from repro.kernels.flash_attention import ops as flash_ops
        return flash_ops.flash_attention(q, k, v, causal=causal,
                                         q_offset=q_offset, scale=scale)
    if bias is None and q.shape[1] >= CHUNKED_THRESHOLD:
        return sdpa_chunked(q, k, v, causal=causal, q_offset=q_offset,
                            scale=scale, chunk=_CHUNK)
    return sdpa_xla(q, k, v, causal=causal, bias=bias, q_offset=q_offset,
                    scale=scale)


# ---------------------------------------------------------------------------
# GQA attention block (dense LMs, ViT with Hkv == Hq)
# ---------------------------------------------------------------------------

def gqa_init(key, d_model: int, n_heads: int, n_kv_heads: int,
             head_dim: int | None = None, *, bias: bool = False,
             dtype=jnp.float32) -> Params:
    head_dim = head_dim or d_model // n_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": linear_init(kq, d_model, n_heads * head_dim, bias=bias,
                          dtype=dtype),
        "wk": linear_init(kk, d_model, n_kv_heads * head_dim,
                          bias=bias, dtype=dtype),
        "wv": linear_init(kv, d_model, n_kv_heads * head_dim,
                          bias=bias, dtype=dtype),
        "wo": linear_init(ko, n_heads * head_dim, d_model, bias=bias,
                          dtype=dtype),
    }


def gqa_qkv(p: Params, x: jnp.ndarray, n_heads: int, n_kv_heads: int):
    B, S, _ = x.shape
    q = linear(p["wq"], x).reshape(B, S, n_heads, -1)
    k = linear(p["wk"], x).reshape(B, S, n_kv_heads, -1)
    v = linear(p["wv"], x).reshape(B, S, n_kv_heads, -1)
    return q, k, v


def gqa_attention(p: Params, x: jnp.ndarray, *, n_heads: int, n_kv_heads: int,
                  angles: jnp.ndarray | None = None, causal: bool = True,
                  impl: str = "xla") -> jnp.ndarray:
    from repro.models.layers import constrain_act
    B, S, _ = x.shape
    q, k, v = gqa_qkv(p, x, n_heads, n_kv_heads)
    if angles is not None:
        q = apply_rope(q, angles[:S])
        k = apply_rope(k, angles[:S])
    # §Perf: keep batch on DP + heads on TP through the attention matmuls
    q = constrain_act(q, (None, "model", None))
    k = constrain_act(k, (None, "model" if n_kv_heads == n_heads else None,
                          None))
    v = constrain_act(v, (None, "model" if n_kv_heads == n_heads else None,
                          None))
    o = sdpa(q, k, v, causal=causal, impl=impl)
    o = constrain_act(o, (None, "model", None))
    return linear(p["wo"], o.reshape(B, S, -1))


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2/V3 style)
# ---------------------------------------------------------------------------
# Queries/keys/values are projected through low-rank latents; the KV cache
# stores only the compressed latent (kv_lora_rank) + a small rope'd key part.

def mla_init(key, d_model: int, n_heads: int, *, q_lora_rank: int,
             kv_lora_rank: int, qk_nope_dim: int, qk_rope_dim: int,
             v_head_dim: int, dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, 8)
    qk_head_dim = qk_nope_dim + qk_rope_dim
    return {
        "wq_a": linear_init(keys[0], d_model, q_lora_rank, bias=False,
                            dtype=dtype),
        "q_a_norm": rmsnorm_init(q_lora_rank, dtype=dtype),
        "wq_b": linear_init(keys[1], q_lora_rank, n_heads * qk_head_dim,
                            bias=False, dtype=dtype),
        "wkv_a": linear_init(keys[2], d_model, kv_lora_rank + qk_rope_dim,
                             bias=False, dtype=dtype),
        "kv_a_norm": rmsnorm_init(kv_lora_rank, dtype=dtype),
        "wkv_b": linear_init(keys[3], kv_lora_rank,
                             n_heads * (qk_nope_dim + v_head_dim),
                             bias=False, dtype=dtype),
        "wo": linear_init(keys[4], n_heads * v_head_dim, d_model, bias=False,
                          dtype=dtype),
    }


def mla_attention(p: Params, x: jnp.ndarray, *, n_heads: int, qk_nope_dim: int,
                  qk_rope_dim: int, v_head_dim: int, kv_lora_rank: int,
                  angles: jnp.ndarray | None = None, causal: bool = True,
                  impl: str = "xla") -> jnp.ndarray:
    """Training/prefill-path MLA (latents expanded; cache-path in
    kvcache.py)."""
    B, S, _ = x.shape
    qk_head_dim = qk_nope_dim + qk_rope_dim

    q_lat = rmsnorm(p["q_a_norm"], linear(p["wq_a"], x))
    q = linear(p["wq_b"], q_lat).reshape(B, S, n_heads, qk_head_dim)
    q_nope, q_rope = q[..., :qk_nope_dim], q[..., qk_nope_dim:]

    kv_a = linear(p["wkv_a"], x)
    kv_lat = rmsnorm(p["kv_a_norm"], kv_a[..., :kv_lora_rank])
    k_rope = kv_a[..., kv_lora_rank:].reshape(B, S, 1, qk_rope_dim)

    kv = linear(p["wkv_b"], kv_lat).reshape(B, S, n_heads,
                                            qk_nope_dim + v_head_dim)
    k_nope, v = kv[..., :qk_nope_dim], kv[..., qk_nope_dim:]

    if angles is not None:
        q_rope = apply_rope(q_rope, angles[:S, : qk_rope_dim // 2])
        k_rope = apply_rope(k_rope, angles[:S, : qk_rope_dim // 2])

    k_rope = jnp.broadcast_to(k_rope, (B, S, n_heads, qk_rope_dim))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope], axis=-1)
    from repro.models.layers import constrain_act
    q_full = constrain_act(q_full, (None, "model", None))
    k_full = constrain_act(k_full, (None, "model", None))
    v = constrain_act(v, (None, "model", None))

    # v_head_dim may differ from qk_head_dim; pad v for the fused kernel path.
    scale = 1.0 / math.sqrt(qk_head_dim)
    if impl == "flash" and v_head_dim != qk_head_dim:
        pad = qk_head_dim - v_head_dim
        v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, max(0, pad))))
        o = sdpa(q_full, k_full, v_p, causal=causal, impl=impl, scale=scale)
        o = o[..., :v_head_dim]
    else:
        o = sdpa(q_full, k_full, v, causal=causal, impl="xla", scale=scale)
    return linear(p["wo"], o.reshape(B, S, n_heads * v_head_dim))


# ---------------------------------------------------------------------------
# Windowed attention (Swin)
# ---------------------------------------------------------------------------

def window_partition(x: jnp.ndarray, window: int) -> jnp.ndarray:
    """[B,H,W,C] -> [B*nW, window*window, C]."""
    B, H, W, C = x.shape
    x = x.reshape(B, H // window, window, W // window, window, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(-1, window * window, C)


def window_unpartition(wins: jnp.ndarray, window: int, H: int,
                       W: int) -> jnp.ndarray:
    B = wins.shape[0] // ((H // window) * (W // window))
    x = wins.reshape(B, H // window, W // window, window, window, -1)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, H, W, -1)


def shifted_window_mask(H: int, W: int, window: int,
                        shift: int) -> jnp.ndarray:
    """Attention bias [nW, window^2, window^2] for shifted windows (Swin)."""
    img = jnp.zeros((1, H, W, 1))
    cnt = 0
    h_slices = ((0, H - window), (H - window, H - shift), (H - shift, H))
    w_slices = ((0, W - window), (W - window, W - shift), (W - shift, W))
    for hs, he in h_slices:
        for ws, we in w_slices:
            img = img.at[:, hs:he, ws:we, :].set(cnt)
            cnt += 1
    wins = window_partition(img, window).squeeze(-1)  # [nW, window^2]
    diff = wins[:, :, None] - wins[:, None, :]
    return jnp.where(diff == 0, 0.0, -1e9)  # [nW, w^2, w^2]


def window_attention(p: Params, x: jnp.ndarray, *, n_heads: int,
                     rel_bias: jnp.ndarray | None = None,
                     mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """x: [nWB, T, C] windows, each image's nW windows together (the
    `window_partition` order); rel_bias: [n_heads, T, T]; mask: [nW, T,
    T], the same for every image, so it broadcasts and is never tiled
    over the batch."""
    nWB, T, C = x.shape
    nW = 1 if mask is None else mask.shape[0]
    q, k, v = (linear(p[w], x).reshape(nWB // nW, nW, T, n_heads, -1)
               .astype(jnp.float32) for w in ("wq", "wk", "wv"))
    logits = jnp.einsum("bwqhd,bwkhd->bwhqk", q, k) / math.sqrt(q.shape[-1])
    if rel_bias is not None:
        logits = logits + rel_bias.astype(jnp.float32)
    if mask is not None:
        logits = logits + mask.astype(jnp.float32)[:, None]
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bwhqk,bwkhd->bwqhd", w, v).astype(x.dtype)
    return linear(p["wo"], o.reshape(nWB, T, C))
