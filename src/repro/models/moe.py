"""Mixture-of-Experts decoder (deepseek-moe-16b, llama4-maverick).

Sort-based token dispatch (no (T,E,C) one-hot tensor): tokens are
argsorted by expert id, placed into per-expert capacity slots, processed
by batched expert matmuls, and combined by scatter-add.  With expert
weights sharded over the ``data`` axis this lowers to the EP all-to-all
pattern; shared experts are merged into one dense MLP.

Layer grouping for scan: ``first_dense`` leading dense layers (deepseek)
run unscanned; the repeating unit (optional dense layer + MoE layer,
``every`` ∈ {1, 2}) is scanned.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, MoESpec
from repro.models import attention as attn
from repro.models import transformer as dense
from repro.models.common import (
    apply_norm,
    cross_entropy,
    dtype_of,
    embed_init,
    he,
    maybe_shard,
    mlp_apply,
    mlp_params,
    norm_params,
)


# ---------------------------------------------------------------------------
# Expert MLP (stacked over E) + sort-based dispatch
# ---------------------------------------------------------------------------
def expert_params(key, E: int, d: int, f: int, act: str, dtype) -> dict:
    keys = jax.random.split(key, E)
    return jax.vmap(lambda k: mlp_params(k, d, f, act, dtype))(keys)


def expert_apply(xs: jax.Array, p: dict, act: str) -> jax.Array:
    """xs: (E, C, D) -> (E, C, D) via per-expert MLP."""
    if "w_gate" in p:
        gate = jnp.einsum("ecd,edf->ecf", xs, p["w_gate"])
        up = jnp.einsum("ecd,edf->ecf", xs, p["w_up"])
        inner = jax.nn.silu(gate) * up
        return jnp.einsum("ecf,efd->ecd", inner, p["w_down"])
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xs, p["w_up"]))
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def moe_mlp(x2d: jax.Array, p: dict, spec: MoESpec, act: str):
    """Routed expert MLP over flat tokens. Returns (y (T,D), aux dict)."""
    T, D = x2d.shape
    E, K = spec.n_experts, spec.top_k
    logits = (x2d.astype(jnp.float32) @ p["router"].astype(jnp.float32))  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_ids = jax.lax.top_k(probs, K)  # (T,K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    C = int(math.ceil(T * K / E * spec.capacity_factor))
    C = max(8, -(-C // 8) * 8)

    flat_ids = gate_ids.reshape(-1)  # (T*K,)
    flat_w = gate_w.reshape(-1)
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    seg_start = jnp.searchsorted(sorted_ids, jnp.arange(E), side="left")
    pos = jnp.arange(T * K) - seg_start[sorted_ids]  # rank within expert
    keep = pos < C
    dest = jnp.where(keep, sorted_ids * C + pos, E * C)  # overflow -> dropped
    src_tok = order // K

    x2d = maybe_shard(x2d, "act_td")
    pulled = maybe_shard(x2d[src_tok], "act_td")  # (T*K, D) token-major
    buf = jnp.zeros((E * C, D), x2d.dtype).at[dest].set(pulled, mode="drop")
    buf = maybe_shard(buf, "act_ecd_flat")  # (E*C, D) expert-major
    expert_in = maybe_shard(buf.reshape(E, C, D), "act_ecd")
    expert_out = expert_apply(expert_in, p["experts"], act)
    expert_out = maybe_shard(expert_out, "act_ecd")
    out_buf = maybe_shard(expert_out.reshape(E * C, D), "act_ecd_flat")

    contrib = maybe_shard(out_buf[jnp.where(keep, dest, 0)], "act_td")
    w = (flat_w[order] * keep).astype(x2d.dtype)
    y = jnp.zeros((T, D), x2d.dtype).at[src_tok].add(contrib * w[:, None])
    y = maybe_shard(y, "act_td")

    if spec.n_shared:
        y = y + mlp_apply(x2d, p["shared"], act)

    # Switch-style load-balance + router z-loss
    top1 = gate_ids[:, 0]
    f_e = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = {
        "lb_loss": E * jnp.sum(f_e * p_e),
        "z_loss": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
        "dropped": jnp.mean(1.0 - keep.astype(jnp.float32)),
        # per-expert dispatch histogram (kept slots only): the access-
        # frequency signal the hotness ledger places expert weights by
        # (core/hotness.py) — routing already computed it for free.
        "expert_counts": jnp.zeros((E,), jnp.float32)
        .at[sorted_ids].add(keep.astype(jnp.float32), mode="drop"),
    }
    return y, aux


def moe_mlp_ep(x2d: jax.Array, p: dict, spec: MoESpec, act: str,
               mesh, dp: tuple[str, ...]):
    """Expert-parallel dispatch via shard_map + all_to_all (the production
    EP pattern): per-shard routing/sort/capacity, one all_to_all to move
    token slots to their expert's shard, local expert matmuls (experts
    stay TP-sharded on the auto ``model`` axis), and the reverse
    all_to_all.  No global sort, no replicated dispatch buffers — this is
    what lets the MoE train/prefill cells fit HBM (EXPERIMENTS.md §Perf).
    """
    import numpy as _np
    T, D = x2d.shape
    E, K = spec.n_experts, spec.top_k
    ndp = int(_np.prod([mesh.shape[a] for a in dp]))
    E_loc = E // ndp
    T_loc = T // ndp
    C = int(math.ceil(T_loc * K / E * spec.capacity_factor))
    C = max(4, -(-C // 4) * 4)

    def local(x_loc, router, experts):
        logits = x_loc.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_ids = jax.lax.top_k(probs, K)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
        flat_ids = gate_ids.reshape(-1)
        flat_w = gate_w.reshape(-1)
        order = jnp.argsort(flat_ids, stable=True)
        sorted_ids = flat_ids[order]
        seg_start = jnp.searchsorted(sorted_ids, jnp.arange(E), side="left")
        pos = jnp.arange(T_loc * K) - seg_start[sorted_ids]
        keep = pos < C
        dest = jnp.where(keep, sorted_ids * C + pos, E * C)
        src_tok = order // K

        buf = jnp.zeros((E * C, D), x2d.dtype).at[dest].set(
            x_loc[src_tok], mode="drop")
        # -> expert shards: (ndp, E_loc*C, D), dim0 = destination shard
        send = buf.reshape(ndp, E_loc * C, D)
        recv = jax.lax.all_to_all(send, dp, 0, 0)  # dim0 = source shard
        ein = recv.reshape(ndp, E_loc, C, D).transpose(1, 0, 2, 3) \
            .reshape(E_loc, ndp * C, D)
        eout = expert_apply(ein, experts, act)
        back = eout.reshape(E_loc, ndp, C, D).transpose(1, 0, 2, 3) \
            .reshape(ndp, E_loc * C, D)
        got = jax.lax.all_to_all(back, dp, 0, 0).reshape(E * C, D)

        contrib = got[jnp.where(keep, dest, 0)]
        w = (flat_w[order] * keep).astype(x2d.dtype)
        y = jnp.zeros((T_loc, D), x2d.dtype).at[src_tok].add(
            contrib * w[:, None])

        top1 = gate_ids[:, 0]
        f_e = jax.lax.pmean(
            jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=0), dp)
        p_e = jax.lax.pmean(jnp.mean(probs, axis=0), dp)
        lb = E * jnp.sum(f_e * p_e)
        zl = jax.lax.pmean(
            jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))), dp)
        dropped = jax.lax.pmean(jnp.mean(1.0 - keep.astype(jnp.float32)), dp)
        counts = jax.lax.psum(
            jnp.zeros((E,), jnp.float32)
            .at[sorted_ids].add(keep.astype(jnp.float32), mode="drop"), dp)
        return y, lb, zl, dropped, counts

    in_specs = (P(dp, None), P(None, None), {
        k: P(dp, None, None) for k in p["experts"]
    })
    out_specs = (P(dp, None), P(), P(), P(), P())
    smap = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(dp))
    y, lb, zl, dropped, counts = smap(x2d, p["router"], p["experts"])
    if spec.n_shared:
        y = y + mlp_apply(x2d, p["shared"], act)
    return y, {"lb_loss": lb, "z_loss": zl, "dropped": dropped,
               "expert_counts": counts}


def _ep_context():
    """(mesh, dp_axes) from the installed activation policy, if EP is on."""
    from repro.models.common import current_policy
    pol = current_policy()
    if pol is None:
        return None
    return pol.get("_ep")


def routed_mlp(x2d: jax.Array, p: dict, spec: MoESpec, act: str):
    """EP shard_map dispatch when a mesh policy provides it; else the
    single-device/auto-spmd path."""
    ep = _ep_context()
    if ep is not None:
        mesh, dp = ep
        import numpy as _np
        ndp = int(_np.prod([mesh.shape[a] for a in dp]))
        if spec.n_experts % ndp == 0 and x2d.shape[0] % ndp == 0:
            return moe_mlp_ep(x2d, p, spec, act, mesh, dp)
    return moe_mlp(x2d, p, spec, act)


def moe_layer_params(cfg: ArchConfig, key) -> dict:
    dt = dtype_of(cfg.param_dtype)
    spec = cfg.moe
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": norm_params(cfg.d_model, cfg.norm, jnp.float32),
        "attn": attn.attn_params(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, dt, cfg.qkv_bias,
        ),
        "ln2": norm_params(cfg.d_model, cfg.norm, jnp.float32),
        "router": he(k2, (cfg.d_model, spec.n_experts), jnp.float32),
        "experts": expert_params(
            k3, spec.n_experts, cfg.d_model, spec.expert_d_ff, cfg.act, dt
        ),
    }
    if spec.n_shared:
        # n parallel shared experts == one MLP with n*f hidden units
        p["shared"] = mlp_params(
            k4, cfg.d_model, spec.n_shared * (spec.shared_d_ff or spec.expert_d_ff),
            cfg.act, dt,
        )
    return p


def _moe_layer_fwd(cfg: ArchConfig, x, lp, positions):
    h = apply_norm(x, lp["ln1"], cfg.norm)
    h = attn.attention(
        h, lp["attn"],
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim, positions=positions,
        causal=True, window=cfg.local_window,
        rope_theta=cfg.rope_theta, rope_pct=cfg.rope_pct, use_rope=cfg.rope,
    )
    x = x + h
    h = apply_norm(x, lp["ln2"], cfg.norm)
    B, S, D = h.shape
    y, aux = routed_mlp(h.reshape(B * S, D), lp, cfg.moe, cfg.act)
    x = x + maybe_shard(y.reshape(B, S, D), "act_btd")
    return x, aux


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------
def _unit_structure(cfg: ArchConfig) -> tuple[int, int, bool]:
    """(n_head_dense, n_units, unit_has_dense)."""
    spec = cfg.moe
    every = spec.every
    n_head = spec.first_dense
    rest = cfg.n_layers - n_head
    assert rest % every == 0, "layer count must fit the MoE pattern"
    return n_head, rest // every, every == 2


def init(cfg: ArchConfig, key) -> dict:
    dt = dtype_of(cfg.param_dtype)
    ke, kd, ku, kh = jax.random.split(key, 4)
    n_head, n_units, has_dense = _unit_structure(cfg)
    params = {
        "embed": embed_init(ke, cfg.vocab_padded, cfg.d_model, dt),
        "final_norm": norm_params(cfg.d_model, cfg.norm, jnp.float32),
    }
    if n_head:
        hk = jax.random.split(kd, n_head)
        params["head_dense"] = jax.vmap(lambda k: dense.init_layer(cfg, k))(hk)
    uk = jax.random.split(ku, n_units)
    unit = {"moe": jax.vmap(lambda k: moe_layer_params(cfg, k))(
        jax.vmap(lambda k: jax.random.fold_in(k, 1))(uk))}
    if has_dense:
        unit["dense"] = jax.vmap(lambda k: dense.init_layer(cfg, k))(
            jax.vmap(lambda k: jax.random.fold_in(k, 0))(uk))
    params["units"] = unit
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(kh, cfg.vocab_padded, cfg.d_model, dt).T
    return params


def forward(cfg: ArchConfig, params: dict, tokens: jax.Array, *,
            prefix_embeds=None, remat: bool = False, last_only: bool = False):
    logits, _aux = forward_with_aux(
        cfg, params, tokens, prefix_embeds=prefix_embeds, remat=remat,
        last_only=last_only,
    )
    return logits


def forward_with_aux(cfg: ArchConfig, params: dict, tokens: jax.Array, *,
                     prefix_embeds=None, remat: bool = False,
                     last_only: bool = False):
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    if prefix_embeds is not None:
        P = prefix_embeds.shape[1]
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x[:, P:]], axis=1)
    x = maybe_shard(x, "act_btd")
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    dense_body = partial(dense._layer_fwd, cfg)
    moe_body = partial(_moe_layer_fwd, cfg)
    if remat:
        dense_body = jax.checkpoint(dense_body)
        moe_body = jax.checkpoint(moe_body)

    if "head_dense" in params:
        def head_fn(x, lp):
            return dense_body(x, lp, positions), None
        x, _ = jax.lax.scan(head_fn, x, params["head_dense"])

    has_dense = "dense" in params["units"]

    def unit_fn(carry, up):
        x, lb, zl = carry
        if has_dense:
            x = dense_body(x, up["dense"], positions)
        x, aux = moe_body(x, up["moe"], positions)
        return (x, lb + aux["lb_loss"], zl + aux["z_loss"]), \
            (aux["dropped"], aux["expert_counts"])

    (x, lb, zl), (dropped, counts) = jax.lax.scan(
        unit_fn, (x, jnp.float32(0), jnp.float32(0)), params["units"]
    )
    if last_only:
        x = x[:, -1:]
    x = apply_norm(x, params["final_norm"], cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = maybe_shard(x @ head, "act_btv")
    n_units = jax.tree_util.tree_leaves(params["units"])[0].shape[0]
    aux = {
        "lb_loss": lb / n_units,
        "z_loss": zl / n_units,
        "dropped": jnp.mean(dropped),
        # summed over the scanned units: (E,) dispatch histogram for the
        # hotness ledger (HotnessLedger.record).
        "expert_counts": counts.sum(axis=0),
    }
    return logits, aux


def loss(cfg: ArchConfig, params: dict, batch: dict, *, remat: bool = False):
    logits, aux = forward_with_aux(
        cfg, params, batch["tokens"],
        prefix_embeds=batch.get("prefix_embeds"), remat=remat,
    )
    nll = cross_entropy(logits, batch["labels"], batch.get("mask"))
    return nll + 0.01 * aux["lb_loss"] + cfg.moe.router_zloss * aux["z_loss"]


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
init_cache = dense.init_cache  # same KV layout (uniform attention stack)


def decode_step(cfg: ArchConfig, params: dict, cache: dict, tokens: jax.Array,
                *, unroll: bool = False):
    """One decode step; fori over the repeating (dense?, moe) unit, or a
    python unroll (``unroll=True``) when the KV cache is large enough that
    the while-loop carry double-buffer matters."""
    x = jnp.take(params["embed"], tokens, axis=0)
    pos = cache["len"]
    n_head, n_units, has_dense = _unit_structure(cfg)
    every = cfg.moe.every

    def attn_step(x, lp, kc, vc):
        h = apply_norm(x[:, None], lp["ln1"], cfg.norm)[:, 0]
        h, kc, vc = attn.decode_attention(
            h, lp["attn"], kc, vc, pos,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, positions=pos,
            rope_theta=cfg.rope_theta, rope_pct=cfg.rope_pct,
            use_rope=cfg.rope, window=cfg.local_window,
        )
        return x + h, kc, vc

    def dense_step(x, lp, kc, vc):
        x, kc, vc = attn_step(x, lp, kc, vc)
        h = apply_norm(x[:, None], lp["ln2"], cfg.norm)[:, 0]
        return x + mlp_apply(h, lp["mlp"], cfg.act), kc, vc

    def moe_step(x, lp, kc, vc):
        x, kc, vc = attn_step(x, lp, kc, vc)
        h = apply_norm(x[:, None], lp["ln2"], cfg.norm)[:, 0]
        y, _aux = routed_mlp(h, lp, cfg.moe, cfg.act)
        return x + y, kc, vc

    # fori + in-place dynamic updates keep the (L,B,T,K,hd) cache a single
    # donated buffer (a scan would double-buffer its carry).
    def _idx(tree, i):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), tree)

    def _layer(carry, li, lp, step):
        x, kc, vc = carry
        ki = jax.lax.dynamic_index_in_dim(kc, li, 0, False)
        vi = jax.lax.dynamic_index_in_dim(vc, li, 0, False)
        x, k2, v2 = step(x, lp, ki, vi)
        kc = jax.lax.dynamic_update_index_in_dim(kc, k2.astype(kc.dtype), li, 0)
        vc = jax.lax.dynamic_update_index_in_dim(vc, v2.astype(vc.dtype), li, 0)
        return x, kc, vc

    carry = (x, cache["k"], cache["v"])
    if n_head:
        def head_body(i, carry):
            return _layer(carry, i, _idx(params["head_dense"], i), dense_step)
        carry = jax.lax.fori_loop(0, n_head, head_body, carry)

    def unit_body(u, carry):
        li = n_head + u * every
        if has_dense:
            carry = _layer(carry, li, _idx(params["units"]["dense"], u), dense_step)
            li = li + 1
        return _layer(carry, li, _idx(params["units"]["moe"], u), moe_step)

    if unroll:
        for u in range(n_units):
            carry = unit_body(u, carry)
        x, k_all, v_all = carry
    else:
        x, k_all, v_all = jax.lax.fori_loop(0, n_units, unit_body, carry)
    x = apply_norm(x[:, None], params["final_norm"], cfg.norm)[:, 0]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    return logits, {"k": k_all, "v": v_all, "len": cache["len"] + 1}
