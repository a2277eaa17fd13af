"""Attention: GQA (full + sliding-window local) and DeepSeek-V2 MLA.

Two tensor-parallel layouts, chosen per-arch by head divisibility
(launch/sharding.py):
  * head-sharded  — heads split over 'model' (Megatron style), when
    n_heads % tp == 0;
  * seq-sharded   — query positions split over 'model' and K/V gathered,
    for ragged head counts (qwen2-7b 28H, musicgen 24H, recurrentgemma 10H).

Long sequences use q-chunked, rematerialized attention (flash-attention via
remat): scores for one query chunk only are ever live; the backward pass
recomputes them. The Pallas flash kernel (kernels/flash_attention.py) is the
TPU runtime path; XLA lowering here is what the dry-run rooflines.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed.context import constrain
from repro.models.layers import apply_rope, dense_init

NEG_INF = -1e30


def gqa_init(key, cfg, dtype):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], (d, H, hd), dtype, fan_in=d),
         "wk": dense_init(ks[1], (d, KV, hd), dtype, fan_in=d),
         "wv": dense_init(ks[2], (d, KV, hd), dtype, fan_in=d),
         "wo": dense_init(ks[3], (H, hd, d), dtype, fan_in=H * hd)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((KV, hd), dtype)
        p["bv"] = jnp.zeros((KV, hd), dtype)
    return p


def _qkv(p, x, cfg, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, q_start, kv_len, window, scale):
    """Scores for q block vs full k/v with causal (+optional window) mask.
    q: (B,c,H,hd) k/v: (B,T,KV,hd). kv_len: valid kv prefix length (int or
    traced scalar). Returns (B,c,H,hd)."""
    B, c, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    rep = H // KV
    qg = q.reshape(B, c, KV, rep, hd)
    s = jnp.einsum("bcgrk,btgk->bgrct", qg.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))               # (B,KV,rep,c,T)
    q_idx = q_start + jnp.arange(c)
    k_idx = jnp.arange(T)
    mask = k_idx[None, :] <= q_idx[:, None]
    mask &= (k_idx < kv_len)[None, :]
    if window is not None:
        mask &= (k_idx[None, :] > q_idx[:, None] - window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrct,btgk->bcgrk", a, v.astype(jnp.float32))
    return o.reshape(B, c, H, hd).astype(q.dtype)


def attend(q, k, v, cfg, q_start=0, kv_len=None, window=None, q_chunk=1024):
    """Causal attention, q-chunked + rematerialized above q_chunk rows."""
    B, S, H, hd = q.shape
    scale = hd ** -0.5
    if kv_len is None:
        kv_len = k.shape[1]
    if S <= q_chunk:
        return _sdpa(q, k, v, q_start, kv_len, window, scale)
    assert S % q_chunk == 0, (S, q_chunk)
    n = S // q_chunk
    qc = q.reshape(B, n, q_chunk, H, hd).swapaxes(0, 1)

    @jax.checkpoint
    def body(i, q_blk):
        return _sdpa(q_blk, k, v, q_start + i * q_chunk, kv_len, window, scale)

    o = jax.lax.map(lambda args: body(*args),
                    (jnp.arange(n), qc))
    return o.swapaxes(0, 1).reshape(B, S, H, hd)


def gqa_apply(p, x, cfg, positions, layout="heads", window=None,
              cache=None, cache_pos=None):
    """Full/local GQA. cache: dict(k,v,(ring) ) for decode; None for train.

    Returns (out, new_cache). For training new_cache is None; for prefill the
    cache dict is created; for decode (x has S==1) the cache is updated at
    cache_pos."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, x, cfg, positions)
    new_cache = None
    if cache is not None and S == 1:
        # decode: append to cache (ring buffer when windowed)
        if window is not None:
            slot = cache_pos % cache["k"].shape[1]
        else:
            slot = cache_pos
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        if window is not None:
            # ring buffer (size = min(window, max_len)): positions are
            # cache_pos-T+1..cache_pos laid out mod T; build per-slot
            # validity+causality mask by absolute position of each slot.
            T = ck.shape[1]
            slots = jnp.arange(T)
            # absolute position stored in each slot
            abs_pos = cache_pos - ((slot - slots) % T)
            mask = (abs_pos >= 0) & (abs_pos <= cache_pos) \
                & (abs_pos > cache_pos - window)
            out = _masked_decode_attend(p, q, k, v, mask)
            return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache
        kv_len = cache_pos + 1
        out = attend(q, k, v, cfg, q_start=cache_pos, kv_len=kv_len,
                     window=None)
    else:
        if cache is not None:   # prefill: return cache of full seq (or window)
            if window is not None:
                Wc = min(window, S)
                new_cache = {"k": k[:, S - Wc:], "v": v[:, S - Wc:]}
            else:
                new_cache = {"k": _seq_shard(k), "v": _seq_shard(v)}
        if layout == "seq" and cfg.attn_impl == "shardmap":
            out = _shardmap_seq_attention(q, k, v, cfg, window)
        elif layout == "heads":
            # KV heads shard over 'model' when divisible (MHA / wide GQA:
            # zero attention collectives); narrow GQA replicates KV.
            from repro.distributed.context import tp_size
            kv_ax = "model" if cfg.n_kv_heads % max(tp_size(), 1) == 0 \
                else None
            q = constrain(q, "batch", None, "model", None)
            k = constrain(k, "batch", None, kv_ax, None)
            v = constrain(v, "batch", None, kv_ax, None)
            out = attend(q, k, v, cfg, window=window)
            out = constrain(out, "batch", None, "model", None)
        else:
            q = constrain(q, "batch", "model", None, None)
            k = constrain(k, "batch", None, None, None)
            v = constrain(v, "batch", None, None, None)
            out = attend(q, k, v, cfg, window=window)
            out = constrain(out, "batch", "model", None, None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


def _shardmap_seq_attention(q, k, v, cfg, window):
    """Explicit sequence-parallel attention (DeepSpeed-Ulysses-shaped) for
    ragged head counts (28H/24H/10H vs tp=16), §Perf hillclimb #1.

    GSPMD cannot shard a 28-head einsum 16 ways and falls back to
    replicating the whole attention on every model shard (~16x redundant
    FLOPs + a full-seq all-gather of q). Here the query axis is explicitly
    shard_map'd over 'model': each device all-gathers the (small, GQA) K/V
    once and computes only its S/16 query block."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.context import batch_axes, get_mesh
    mesh = get_mesh()
    baxes = batch_axes()
    spec = P(baxes, "model", None, None)

    def f(qb, kb, vb):
        kf = jax.lax.all_gather(kb, "model", axis=1, tiled=True)
        vf = jax.lax.all_gather(vb, "model", axis=1, tiled=True)
        S_loc = qb.shape[1]
        start = jax.lax.axis_index("model") * S_loc
        return attend(qb, kf, vf, cfg, q_start=start, kv_len=kf.shape[1],
                      window=window, q_chunk=min(1024, S_loc))

    return jax.shard_map(f, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _seq_shard(t, axis=1):
    """Shard a long cache seq dim over 'model' (2D KV-cache sharding: batch
    x seq) — required for 32k caches of 100B+ archs to fit HBM."""
    from repro.distributed.context import get_mesh, tp_axis
    tp = get_mesh().shape[tp_axis()]
    S = t.shape[axis]
    if S >= 4096 and S % tp == 0:
        spec = ["batch"] + [None] * (t.ndim - 1)
        spec[axis] = "model"
        return constrain(t, *spec)
    return t


def _masked_decode_attend(p, q, k, v, mask):
    """q (B,1,H,hd); k/v (B,T,KV,hd); mask (T,) bool."""
    B, _, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    qg = q.reshape(B, KV, rep, hd)
    s = jnp.einsum("bgrk,btgk->bgrt", qg.astype(jnp.float32) * hd ** -0.5,
                   k.astype(jnp.float32))
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrt,btgk->bgrk", a, v.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def gqa_cache_shape(cfg, batch, max_len, window=None, dtype=jnp.bfloat16):
    T = min(window, max_len) if window else max_len
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {"k": jnp.zeros((batch, T, kv, hd), dtype),
            "v": jnp.zeros((batch, T, kv, hd), dtype)}


# ===================================================================== MLA
def mla_init(key, cfg, dtype):
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 8)
    return {
        "w_dq": dense_init(ks[0], (d, m.q_lora_rank), dtype),
        "w_uq": dense_init(ks[1], (m.q_lora_rank, H,
                                   m.qk_nope_head_dim + m.qk_rope_head_dim),
                           dtype, fan_in=m.q_lora_rank),
        "w_dkv": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                            dtype),
        "w_uk": dense_init(ks[3], (m.kv_lora_rank, H, m.qk_nope_head_dim),
                           dtype, fan_in=m.kv_lora_rank),
        "w_uv": dense_init(ks[4], (m.kv_lora_rank, H, m.v_head_dim), dtype,
                           fan_in=m.kv_lora_rank),
        "w_o": dense_init(ks[5], (H, m.v_head_dim, d), dtype,
                          fan_in=H * m.v_head_dim),
        "q_norm": {"scale": jnp.ones((m.q_lora_rank,), dtype)},
        "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,), dtype)},
    }


def _mla_compress(p, x, cfg, positions):
    """Down-projections shared by all MLA paths. Returns (cq, c_kv, k_rope)."""
    from repro.models.layers import apply_norm
    m = cfg.mla
    cq = apply_norm(p["q_norm"], jnp.einsum("bsd,dr->bsr", x, p["w_dq"]))
    dkv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    c_kv = apply_norm(p["kv_norm"], dkv[..., :m.kv_lora_rank])
    k_rope = apply_rope(dkv[..., m.kv_lora_rank:], positions, cfg.rope_theta)
    return cq, c_kv, k_rope


def mla_apply(p, x, cfg, positions, cache=None, cache_pos=None):
    """MLA. Train/prefill: naive (decompressed) form. Decode: absorbed form
    against the compressed cache (c_kv, k_rope) — the paper-relevant memory
    saving of MLA."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    cq, c_kv_new, k_rope_new = _mla_compress(p, x, cfg, positions)
    q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"])
    q_nope, q_rope = (q[..., :m.qk_nope_head_dim],
                      apply_rope(q[..., m.qk_nope_head_dim:], positions,
                                 cfg.rope_theta))
    if cache is not None and S == 1:
        ck = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv_new,
                                                 cache_pos, axis=1)
        cr = jax.lax.dynamic_update_slice_in_dim(cache["k_rope"], k_rope_new,
                                                 cache_pos, axis=1)
        new_cache = {"c_kv": ck, "k_rope": cr}
        # absorbed: q~ = q_nope @ w_uk  -> score in latent space
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"])
        s = (jnp.einsum("bshr,btr->bhst", q_lat.astype(jnp.float32),
                        ck.astype(jnp.float32))
             + jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32),
                          cr.astype(jnp.float32))) * scale
        t_idx = jnp.arange(ck.shape[1])
        s = jnp.where((t_idx <= cache_pos)[None, None, None], s, NEG_INF)
        a = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhst,btr->bshr", a, ck.astype(jnp.float32))
        o = jnp.einsum("bshr,rhk->bshk", o_lat.astype(x.dtype), p["w_uv"])
        return jnp.einsum("bshk,hkd->bsd", o, p["w_o"]), new_cache
    # naive (train / prefill)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv_new, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv_new, p["w_uv"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope_new[:, :, None, :],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)
    qq = constrain(qq, "batch", None, "model", None)
    k = constrain(k, "batch", None, "model", None)
    v = constrain(v, "batch", None, "model", None)
    # pad v to qk head dim for the shared attend() then slice back
    out = attend(qq, k, _pad_last(v, qq.shape[-1]), cfg)
    out = out[..., :m.v_head_dim]
    new_cache = None
    if cache is not None:
        new_cache = {"c_kv": c_kv_new, "k_rope": k_rope_new}
    return jnp.einsum("bshk,hkd->bsd", out, p["w_o"]), new_cache


def _pad_last(x, dim):
    pad = dim - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def mla_cache_shape(cfg, batch, max_len, dtype=jnp.bfloat16):
    m = cfg.mla
    return {"c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype)}
