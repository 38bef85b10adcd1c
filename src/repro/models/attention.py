"""GQA attention: training (chunked, exact), decode (KV cache), tiered merge.

Training attention chunks the query axis through ``lax.scan`` so the
materialized score block is (chunk, S) instead of (S, S) — the memory
shape a flash kernel gives on TPU, expressed portably.  Decode attention
supports full, local (ring-buffer), and cross variants, and exposes
``attend_partial`` + ``merge_partials`` so a KV cache split across
memory tiers (the paper's N:M interleave) combines exactly.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import apply_rope, he, maybe_shard

NEG_INF = -1e30


def attn_params(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
                dtype, qkv_bias: bool = False) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": he(kq, (d_model, n_heads * head_dim), dtype),
        "wk": he(kk, (d_model, n_kv_heads * head_dim), dtype),
        "wv": he(kv, (d_model, n_kv_heads * head_dim), dtype),
        "wo": he(ko, (n_heads * head_dim, d_model), dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


def _project_qkv(x, p, n_heads, n_kv_heads, head_dim):
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, S, n_kv_heads, head_dim)
    v = v.reshape(B, S, n_kv_heads, head_dim)
    return q, k, v


def _gqa_scores(q, k):
    """q: (B,Sq,H,hd), k: (B,Sk,K,hd) -> scores (B,K,H/K,Sq,Sk)."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    qg = q.reshape(B, Sq, K, H // K, hd)
    return jnp.einsum("bqkgh,bskh->bkgqs", qg, k) / np.sqrt(hd)


def _gqa_out(probs, v):
    """probs: (B,K,G,Sq,Sk), v: (B,Sk,K,hd) -> (B,Sq,H,hd)."""
    B, K, G, Sq, Sk = probs.shape
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Sq, K * G, v.shape[-1])


def attention(
    x: jax.Array,
    p: dict,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    positions: jax.Array,  # (B, S)
    causal: bool = True,
    window: int = 0,  # 0 = full
    rope_theta: float = 10_000.0,
    rope_pct: float = 1.0,
    use_rope: bool = True,
    q_chunk: int = 1024,
    kv_override: Optional[tuple] = None,  # cross-attention (k, v, kv_positions)
) -> jax.Array:
    """Full-sequence attention; exact, q-chunked. Returns (B, S, D)."""
    from repro.models.common import current_policy
    pol = current_policy()
    if pol and "_q_chunk" in pol:
        q_chunk = pol["_q_chunk"]
    B, S, _ = x.shape
    q, k, v = _project_qkv(x, p, n_heads, n_kv_heads, head_dim)
    if kv_override is not None:
        k, v, kv_pos = kv_override
        causal = False
    else:
        kv_pos = positions
        if use_rope:
            k = apply_rope(k, kv_pos, rope_theta, rope_pct)
    if use_rope:
        q = apply_rope(q, positions, rope_theta, rope_pct)
    q = maybe_shard(q, "act_bshd")
    k = maybe_shard(k, "act_bskd")
    v = maybe_shard(v, "act_bskd")

    def block_exact(q_blk, pos_blk):
        scores = _gqa_scores(q_blk, k)  # (B,K,G,C,Sk)
        mask = jnp.ones((B, pos_blk.shape[1], kv_pos.shape[1]), bool)
        if causal:
            mask &= pos_blk[:, :, None] >= kv_pos[:, None, :]
        if window:
            mask &= pos_blk[:, :, None] - kv_pos[:, None, :] < window
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
        return _gqa_out(probs, v)  # (B,C,H,hd)

    kv_chunk = int(pol.get("_kv_chunk", 1024)) if pol else 1024

    def block_flash(q_blk, pos_blk):
        """Online-softmax over KV chunks: the (C, S_kv) score tensor never
        materializes — only (C, kv_chunk) blocks, sized to stay
        VMEM-resident on TPU (EXPERIMENTS.md §Perf, flash iteration)."""
        Sk = k.shape[1]
        nkv = Sk // kv_chunk
        C = q_blk.shape[1]
        K = k.shape[2]
        G = q_blk.shape[2] // K
        kc = jnp.moveaxis(k.reshape(B, nkv, kv_chunk, K, hd_), 1, 0)
        vc = jnp.moveaxis(v.reshape(B, nkv, kv_chunk, K, hd_), 1, 0)
        pc = jnp.moveaxis(kv_pos.reshape(B, nkv, kv_chunk), 1, 0)

        def body(carry, inp):
            acc, m, l = carry
            kj, vj, pj = inp
            s = _gqa_scores(q_blk, kj).astype(jnp.float32)  # (B,K,G,C,ck)
            mask = jnp.ones((B, C, kv_chunk), bool)
            if causal:
                mask &= pos_blk[:, :, None] >= pj[:, None, :]
            if window:
                mask &= pos_blk[:, :, None] - pj[:, None, :] < window
            s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p_ = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p_, axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p_.astype(vj.dtype), vj)
            acc = acc * alpha[..., None] + pv.astype(jnp.float32)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, K, G, C, hd_), jnp.float32)
        m0 = jnp.full((B, K, G, C), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, C), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (kc, vc, pc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,K,G,C,hd)
        return out.transpose(0, 3, 1, 2, 4).reshape(
            B, C, K * G, hd_).astype(x.dtype)

    hd_ = head_dim
    use_flash = bool(pol and pol.get("_flash")) \
        and k.shape[1] % kv_chunk == 0 and k.shape[1] > kv_chunk
    block = block_flash if use_flash else block_exact

    if S % q_chunk:
        # largest divisor of S that is <= q_chunk (whisper's 1500-frame
        # encoder etc.); 1 leaves attention unchunked
        q_chunk = max(d for d in range(1, q_chunk + 1) if S % d == 0)
    if S <= q_chunk or q_chunk == 1:
        out = block(q, positions)
    else:
        n = S // q_chunk
        qs = q.reshape(B, n, q_chunk, n_heads, head_dim).transpose(1, 0, 2, 3, 4)
        ps = positions.reshape(B, n, q_chunk).transpose(1, 0, 2)
        def body(_, qp):
            return None, block(qp[0], qp[1])
        _, outs = jax.lax.scan(body, None, (qs, ps))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, S, n_heads, head_dim)
    out = out.reshape(B, S, n_heads * head_dim)
    return out @ p["wo"]


# ---------------------------------------------------------------------------
# Decode path (one new token against a KV cache)
# ---------------------------------------------------------------------------
def attend_partial(q, k, v, valid: jax.Array):
    """Unnormalized attention over one KV partition.

    q: (B,H,hd); k,v: (B,T,K,hd); valid: (B,T) bool.
    Returns (acc (B,H,hd), lse-pieces (m, l): (B,H)).
    """
    B, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgh,btkh->bkgt", qg,
                        k.astype(jnp.float32)) / np.sqrt(hd)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)  # (B,K,G)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgt,btkh->bkgh", p, v.astype(jnp.float32))
    return acc.reshape(B, H, hd), m.reshape(B, H), l.reshape(B, H)


def merge_partials(parts):
    """Exactly merge [(acc, m, l), ...] partial attentions (flash combine)."""
    accs, ms, ls = zip(*parts)
    m_all = jnp.max(jnp.stack(ms), axis=0)  # (B,H)
    acc_t, l_t = 0.0, 0.0
    for acc, m, l in parts:
        w = jnp.exp(m - m_all)
        acc_t = acc_t + acc * w[..., None]
        l_t = l_t + l * w
    return acc_t / jnp.maximum(l_t, 1e-30)[..., None]


def attend_chunk(q, k, v, valid: jax.Array):
    """Attention of a chunk of one sequence's queries over its KV view,
    with the float32 accumulation of ``attend_partial`` and
    ``merge_partials`` for a single partition.

    q: (C,H,hd); k,v: (T,K,hd); valid: (C,T) bool.  Returns (C,H,hd)."""
    C, H, hd = q.shape
    K = k.shape[1]
    G = H // K
    qg = q.reshape(C, K, G, hd).astype(jnp.float32)
    scores = jnp.einsum("ckgh,tkh->ckgt", qg,
                        k.astype(jnp.float32)) / np.sqrt(hd)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("ckgt,tkh->ckgh", p, v.astype(jnp.float32))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).reshape(C, H, hd)


def decode_attention(
    x_tok: jax.Array,  # (B, D) current token activations
    p: dict,
    k_cache: jax.Array,  # (B, T, K, hd)
    v_cache: jax.Array,
    cache_len: jax.Array,  # (B,) valid prefix length (pre-append)
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    positions: jax.Array,  # (B,) absolute position of the new token
    rope_theta: float = 10_000.0,
    rope_pct: float = 1.0,
    use_rope: bool = True,
    window: int = 0,  # ring-buffer semantics when > 0
    extra_partitions: tuple = (),  # [(k, v, valid)] e.g. the slow-tier split
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step. Returns (out (B,D), new_k_cache, new_v_cache)."""
    B, D = x_tok.shape
    q = (x_tok @ p["wq"])
    k = (x_tok @ p["wk"])
    v = (x_tok @ p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, n_heads, head_dim)
    k = k.reshape(B, n_kv_heads, head_dim)
    v = v.reshape(B, n_kv_heads, head_dim)
    if use_rope:
        q = apply_rope(q[:, None], positions[:, None], rope_theta, rope_pct)[:, 0]
        k = apply_rope(k[:, None], positions[:, None], rope_theta, rope_pct)[:, 0]
    T = k_cache.shape[1]
    slot = (cache_len % T) if window else jnp.minimum(cache_len, T - 1)
    bidx = jnp.arange(B)
    k_cache = k_cache.at[bidx, slot].set(k.astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, slot].set(v.astype(v_cache.dtype))
    t_idx = jnp.arange(T)[None, :]
    if window:
        valid = t_idx < jnp.minimum(cache_len + 1, T)[:, None]
    else:
        valid = t_idx <= cache_len[:, None]
    parts = [attend_partial(q, k_cache, v_cache, valid)]
    for (ke, ve, vald) in extra_partitions:
        parts.append(attend_partial(q, ke, ve, vald))
    out = merge_partials(parts).astype(x_tok.dtype)  # (B,H,hd)
    return out.reshape(B, n_heads * head_dim) @ p["wo"], k_cache, v_cache
