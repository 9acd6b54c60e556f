"""Building blocks: norms, RoPE, attention (MHA/GQA/MLA), MLPs.

Pure-JAX functional style: ``init_*`` return param pytrees (dicts of
``jnp.ndarray``); ``apply`` functions are stateless.  Compute dtype is
bf16 (config), params are fp32; softmax/normalization run in fp32.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig

Params = Dict[str, Any]


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, in_dim: int, out_shape: Tuple[int, ...], dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(in_dim)
    return jax.random.normal(key, (in_dim, *out_shape), dtype=dtype) * scale


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), pdtype(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), pdtype(cfg))
    return p


def apply_norm(p: Params, x: jnp.ndarray, cfg: ModelConfig, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, D) rotate pairs; positions: (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, d/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def sinusoidal_embedding(length: int, dim: int) -> jnp.ndarray:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32) * (-math.log(10000.0) / dim))
    emb = jnp.zeros((length, dim), jnp.float32)
    emb = emb.at[:, 0::2].set(jnp.sin(pos * div))
    emb = emb.at[:, 1::2].set(jnp.cos(pos * div))
    return emb


# ---------------------------------------------------------------------------
# Attention (MHA / GQA)
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig) -> Params:
    a = cfg.attention
    assert a is not None
    d, dt = cfg.d_model, pdtype(cfg)
    ks = jax.random.split(key, 8)
    if a.kind == "mla":
        rd, nd, vd = a.qk_rope_head_dim, a.qk_nope_head_dim, a.v_head_dim
        p: Params = {
            "wq_a": dense_init(ks[0], d, (a.q_lora_rank,), dt),
            "q_norm": jnp.ones((a.q_lora_rank,), dt),
            "wq_b": dense_init(ks[1], a.q_lora_rank, (a.num_heads, nd + rd), dt),
            "wkv_a": dense_init(ks[2], d, (a.kv_lora_rank,), dt),
            "kv_norm": jnp.ones((a.kv_lora_rank,), dt),
            "wk_rope": dense_init(ks[3], d, (rd,), dt),
            "wkv_b": dense_init(ks[4], a.kv_lora_rank, (a.num_heads, nd + vd), dt),
            "wo": dense_init(ks[5], a.num_heads * vd, (d,), dt).reshape(a.num_heads, vd, d),
        }
        return p
    hd = a.head_dim
    return {
        "wq": dense_init(ks[0], d, (a.num_heads, hd), dt),
        "wk": dense_init(ks[1], d, (a.num_kv_heads, hd), dt),
        "wv": dense_init(ks[2], d, (a.num_kv_heads, hd), dt),
        "wo": dense_init(ks[3], a.num_heads * hd, (d,), dt).reshape(a.num_heads, hd, d),
    }


def _sdpa_dense(q, k, v, *, causal: bool, q_offset, kv_len: Optional[jnp.ndarray] = None):
    """q: (B,S,Hkv,G,D) k,v: (B,T,Hkv,Dk/Dv). fp32 softmax, bf16 matmuls.

    q_offset: position of q[0] — scalar, or (B,) for per-slot decode
    (continuous batching).  kv_len: valid cache length (scalar or (B,));
    positions >= kv_len are masked out.

    Context parallelism: when the kv-head count cannot shard over the model
    axis (e.g. 8 KV heads on a 16-wide axis), the score/AV compute would
    replicate across it.  We instead shard K/V and the score tile along T
    ("tp" on the sequence dim — ring-attention layout); GSPMD inserts the
    max/sum reductions for the T-sharded softmax and the AV partial-sum
    all-reduce.  Engaged automatically via seq-shard constraints below.
    """
    B, S, Hkv, G, D = q.shape
    T = k.shape[1]
    from repro.models.sharding import constrain, tp_divides

    # scores keep (Hkv, G) as separate dims, so head sharding needs Hkv
    # itself to divide the axis — a divisible Hkv*G product doesn't help.
    seq_shard = not tp_divides(Hkv)
    if seq_shard:
        k = constrain(k, "dp", "tp", None, None)
        v = constrain(v, "dp", "tp", None, None)
    scale = 1.0 / math.sqrt(D)
    scores = jnp.einsum("bshgd,bthd->bhgst", q, k) * scale  # (B,Hkv,G,S,T)
    if seq_shard:
        scores = constrain(scores, "dp", None, None, None, "tp")
    scores = scores.astype(jnp.float32)
    tpos = jnp.arange(T)
    mask = None  # (B|1, S, T)
    if causal:
        qpos = jnp.arange(S)[None, :] + jnp.atleast_1d(q_offset)[:, None]  # (B|1,S)
        mask = tpos[None, None, :] <= qpos[:, :, None]
    if kv_len is not None:
        valid = tpos[None, None, :] < jnp.atleast_1d(kv_len)[:, None, None]  # (B|1,1,T)
        mask = valid if mask is None else (mask & valid)
    if mask is not None:
        scores = jnp.where(mask[:, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgst,bthd->bshgd", w, v)  # (B,S,Hkv,G,Dv)
    return out


def _sdpa(q, k, v, *, causal: bool, q_offset, kv_len: Optional[jnp.ndarray] = None,
          impl: str = "ref"):
    """Dispatch: dense tile for short q, flash-style q-chunked for long q
    (static shape decision — resolved at trace time).  ``impl="pallas"``
    routes the no-cache causal self-attention path through the Pallas flash
    kernel (compiled for the TPU); ``impl="pallas_interpret"`` runs the same
    kernel in interpret mode (CPU hosts, tests)."""
    S = q.shape[1]
    if (
        impl in ("pallas", "pallas_interpret")
        and kv_len is None
        and causal
        and S == k.shape[1]  # full self-attention (train / whole prefill)
    ):
        from repro.kernels.flash_attention.ops import flash_attention

        B, _, Hkv, G, D = q.shape
        qf = q.reshape(B, S, Hkv * G, D).transpose(0, 2, 1, 3)  # (B,Hq,S,D)
        kf = k.transpose(0, 2, 1, 3)  # (B,Hkv,T,D)
        vf = v.transpose(0, 2, 1, 3)
        out = flash_attention(qf, kf, vf, causal=True,
                              interpret=impl == "pallas_interpret")
        return out.transpose(0, 2, 1, 3).reshape(B, S, Hkv, G, D)
    if S >= CHUNKED_SDPA_THRESHOLD and S % 1024 == 0:
        return _sdpa_chunked(q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len)
    return _sdpa_dense(q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len)


def _cache_update(cache: Params, k: jnp.ndarray, v: jnp.ndarray, cache_pos):
    """Write k/v at cache_pos.  Scalar pos: one slice update; vector pos
    (B,): per-slot writes via vmap (continuous batching)."""
    kc, vc = cache["k"], cache["v"]
    k = k.astype(kc.dtype)
    v = v.astype(vc.dtype)
    if getattr(cache_pos, "ndim", 0) == 1:
        upd = jax.vmap(lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0))
        return upd(kc, k, cache_pos), upd(vc, v, cache_pos)
    return (
        jax.lax.dynamic_update_slice_in_dim(kc, k, cache_pos, axis=1),
        jax.lax.dynamic_update_slice_in_dim(vc, v, cache_pos, axis=1),
    )


CHUNKED_SDPA_THRESHOLD = 4_096  # q length above which flash-style chunking kicks in


def _sdpa_chunked(q, k, v, *, causal: bool, q_offset, kv_len=None, chunk: int = 1024):
    """Flash-style O(S) memory SDPA in pure jnp: lax.scan over q chunks, so
    only a (chunk x T) score tile is live — the compile-time stand-in for
    the Pallas flash kernel on long sequences (prefill_32k and train-long).
    """
    B, S, Hkv, G, D = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nq = S // chunk
    qc = q.reshape(B, nq, chunk, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)

    def body(carry, inp):
        qi, q_blk = inp
        off = q_offset + qi * chunk
        out = _sdpa_dense(q_blk, k, v, causal=causal, q_offset=off, kv_len=kv_len)
        return carry, out

    _, outs = jax.lax.scan(body, None, (jnp.arange(nq), qc))
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Hkv, G, v.shape[-1])


def apply_attention(
    p: Params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    positions: jnp.ndarray,
    causal: bool = True,
    cache: Optional[Params] = None,
    cache_pos: Optional[jnp.ndarray] = None,
    kv_source: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """GQA/MHA attention.  If ``cache`` is given, (k,v) are written at
    ``cache_pos`` and attention runs over the cache (decode/serving path).
    ``kv_source`` (cross-attention) computes k,v from a different sequence.
    """
    a = cfg.attention
    assert a is not None and a.kind in ("mha", "gqa")
    B, S, d = x.shape
    src = x if kv_source is None else kv_source
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(x.dtype))
    if a.rope and kv_source is None:
        q = apply_rope(q, positions, a.rope_theta)
        k = apply_rope(k, positions, a.rope_theta)
    kv_len = None
    if cache is not None:
        if kv_source is None:  # self-attention cache update
            k, v = _cache_update(cache, k, v, cache_pos)
            cache = {"k": k, "v": v}
            kv_len = cache_pos + S
        else:  # cross-attention: cache holds precomputed enc k/v
            k, v = cache["k"], cache["v"]
    G = a.q_heads_per_kv
    qg = q.reshape(B, S, a.num_kv_heads, G, a.head_dim)
    q_offset = positions[0] if positions.ndim == 1 else positions[:, 0]
    out = _sdpa(qg, k.astype(x.dtype), v.astype(x.dtype), causal=causal,
                q_offset=q_offset, kv_len=kv_len, impl=cfg.attention_impl)
    out = out.reshape(B, S, a.num_heads, a.head_dim)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, cache


MLA_ABSORB_MAX_S = 64  # decode/small-S: absorbed-matmul MLA (0 disables)


def apply_mla_attention(
    p: Params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    positions: jnp.ndarray,
    causal: bool = True,
    cache: Optional[Params] = None,
    cache_pos: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Multi-head latent attention (MiniCPM3/DeepSeek-V2).

    The KV cache stores only the compressed latent (kv_lora_rank) + the
    shared rope key (qk_rope_head_dim) — the MLA memory win for decode.
    """
    a = cfg.attention
    assert a is not None and a.kind == "mla"
    B, S, d = x.shape
    rd, nd, vd = a.qk_rope_head_dim, a.qk_nope_head_dim, a.v_head_dim
    H = a.num_heads

    def rms(z, scale):
        zf = z.astype(jnp.float32)
        return (zf * jax.lax.rsqrt((zf * zf).mean(-1, keepdims=True) + 1e-6)
                * scale.astype(jnp.float32)).astype(z.dtype)

    cq = rms(jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(x.dtype)), p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"].astype(x.dtype))  # (B,S,H,nd+rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, a.rope_theta)

    c_kv = rms(jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype)), p["kv_norm"])
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["wk_rope"].astype(x.dtype))[:, :, None]
    k_rope = apply_rope(k_rope, positions, a.rope_theta)[:, :, 0]  # (B,S,rd)

    kv_len = None
    if cache is not None:
        if getattr(cache_pos, "ndim", 0) == 1:
            upd = jax.vmap(
                lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0)
            )
            c_kv = upd(cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), cache_pos)
            k_rope = upd(cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), cache_pos)
        else:
            c_kv = jax.lax.dynamic_update_slice_in_dim(
                cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), cache_pos, axis=1)
            k_rope = jax.lax.dynamic_update_slice_in_dim(
                cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), cache_pos, axis=1)
        cache = {"c_kv": c_kv, "k_rope": k_rope}
        kv_len = cache_pos + S

    if cache is not None and S <= MLA_ABSORB_MAX_S:
        # Absorbed-matmul decode (DeepSeek-V2 MLA): attention runs in the
        # LATENT space — wkv_b's key half is absorbed into the query and its
        # value half into the output, so the cached latent is never expanded
        # to (B,T,H,nd+vd).  Per decoded token this removes the
        # O(T*r*H*(nd+vd)) expansion (~50-100x decode FLOPs; see §Perf).
        wkv_b = p["wkv_b"].astype(x.dtype)  # (r, H, nd+vd)
        wk_b, wv_b = wkv_b[..., :nd], wkv_b[..., nd:]
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wk_b)  # (B,S,H,r)
        ckv = c_kv.astype(x.dtype)  # (B, T, r) — the cache itself
        krt = k_rope.astype(x.dtype)  # (B, T, rd)
        scale = 1.0 / math.sqrt(nd + rd)
        scores = (
            jnp.einsum("bshr,btr->bhst", q_lat, ckv)
            + jnp.einsum("bshr,btr->bhst", q_rope, krt)
        ).astype(jnp.float32) * scale
        T = ckv.shape[1]
        tpos = jnp.arange(T)
        qpos = jnp.arange(S)[None, :] + jnp.atleast_1d(
            positions[0] if positions.ndim == 1 else positions[:, 0]
        )[:, None]
        mask = tpos[None, None, :] <= qpos[:, :, None]
        if kv_len is not None:
            mask = mask & (tpos[None, None, :] < jnp.atleast_1d(kv_len)[:, None, None])
        scores = jnp.where(mask[:, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out_lat = jnp.einsum("bhst,btr->bshr", w, ckv)  # (B,S,H,r)
        out = jnp.einsum("bshr,rhv->bshv", out_lat, wv_b)  # (B,S,H,vd)
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
        return y, cache

    kv = jnp.einsum("btr,rhk->bthk", c_kv.astype(x.dtype), p["wkv_b"].astype(x.dtype))
    k_nope, v = kv[..., :nd], kv[..., nd:]
    T = k_nope.shape[1]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :].astype(x.dtype), (B, T, H, rd))],
        axis=-1,
    )
    qh = jnp.concatenate([q_nope, q_rope], -1).reshape(B, S, H, 1, nd + rd)
    q_offset = positions[0] if positions.ndim == 1 else positions[:, 0]
    out = _sdpa(qh, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len)
    out = out.reshape(B, S, H, vd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None, d_in: Optional[int] = None) -> Params:
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    dt = pdtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.mlp == "swiglu":
        return {
            "w_gate": dense_init(k1, d, (f,), dt),
            "w_up": dense_init(k2, d, (f,), dt),
            "w_down": dense_init(k3, f, (d,), dt),
        }
    return {  # relu2 | gelu
        "w_up": dense_init(k1, d, (f,), dt),
        "w_down": dense_init(k2, f, (d,), dt),
    }


def apply_mlp(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.mlp == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
        h = jax.nn.silu(g) * u
    else:
        h = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
        if cfg.mlp == "relu2":  # nemotron squared-ReLU
            h = jnp.square(jax.nn.relu(h))
        else:
            h = jax.nn.gelu(h)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def init_embedding(key, cfg: ModelConfig) -> Params:
    return {"w": jax.random.normal(key, (cfg.vocab_size, cfg.d_model), pdtype(cfg)) * 0.02}


def apply_embedding(p: Params, tokens: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    return jnp.take(p["w"].astype(cdtype(cfg)), tokens, axis=0)


def init_lm_head(key, cfg: ModelConfig) -> Params:
    return {"w": dense_init(key, cfg.d_model, (cfg.vocab_size,), pdtype(cfg))}


def apply_lm_head(p: Params, x: jnp.ndarray, cfg: ModelConfig, embed: Optional[Params] = None) -> jnp.ndarray:
    if cfg.tie_embeddings:
        assert embed is not None
        w = embed["w"].astype(x.dtype).T
    else:
        w = p["w"].astype(x.dtype)
    return jnp.einsum("bsd,dv->bsv", x, w)


def cross_entropy_loss(
    logits: jnp.ndarray, targets: jnp.ndarray, label_smoothing: float = 0.0
) -> jnp.ndarray:
    """Mean token CE in fp32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    loss = logz - gold
    if label_smoothing:
        mean_all = logz - logits.mean(-1)
        loss = (1 - label_smoothing) * loss + label_smoothing * mean_all
    return loss.mean()
