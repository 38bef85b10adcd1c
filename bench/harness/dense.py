"""Weights from the seed, and the plain float32 reference, for the dense
decoder family (pre-norm blocks of GQA attention with RoPE and a
two-matrix MLP).

``make_params`` builds the weights the program serves, in its parameter
layout and in the dtype the configuration states, on the device in one
jitted call.  ``reference`` rebuilds them layer by layer from the same
seed, in float32, and runs the forward pass at ``highest`` matmul
precision.  It imports nothing of the program and takes nothing the
program has made.  The equations are the program's as its configuration
states them: layer norm (eps 1e-5) or RMS norm (eps 1e-6); RoPE that
rotates the (even, odd) pairs of each head; tanh-approximated GELU or
SwiGLU; causal softmax attention; the output head tied to the embedding
or its own matrix, as the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


def base_key(seed: int) -> jax.Array:
    """A key for any whole ``seed`` up to 2**62."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, int(seed) & 0x7FFFFFFF)
    return jax.random.fold_in(key, (int(seed) >> 31) & 0x7FFFFFFF)


def _dims(cfg: dict):
    hd = cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]
    vocab_padded = -(-cfg["vocab"] // 128) * 128
    return cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], hd, \
        cfg["d_ff"], vocab_padded


def _norm_leaves(cfg: dict) -> list[str]:
    return ["scale"] if cfg["norm"] == "rmsnorm" else ["scale", "bias"]


def layer_leaves(cfg: dict) -> list[tuple[tuple[str, ...], tuple, float,
                                          float, bool]]:
    """``(path, shape, mean, std, in_param_dtype)`` of each weight of one
    layer, in a fixed order that numbers the weights.  The query and key
    weights are drawn ``init_qk_gain`` times wider than the others, where
    the configuration says so."""
    D, H, K, hd, F, _ = _dims(cfg)
    qk = cfg.get("init_qk_gain", 1.0)
    out = []
    for blk in ("ln1", "ln2"):
        for leaf in _norm_leaves(cfg):
            mean = 1.0 if leaf == "scale" else 0.0
            out.append(((blk, leaf), (D,), mean, 0.1, False))
    out += [(("attn", "wq"), (D, H * hd), 0.0, qk / math.sqrt(D), True),
            (("attn", "wk"), (D, K * hd), 0.0, qk / math.sqrt(D), True),
            (("attn", "wv"), (D, K * hd), 0.0, 1 / math.sqrt(D), True),
            (("attn", "wo"), (H * hd, D), 0.0, 1 / math.sqrt(H * hd), True)]
    if cfg.get("qkv_bias"):
        out += [(("attn", "bq"), (H * hd,), 0.0, 0.02, True),
                (("attn", "bk"), (K * hd,), 0.0, 0.02, True),
                (("attn", "bv"), (K * hd,), 0.0, 0.02, True)]
    if cfg["act"] in ("swiglu", "geglu"):
        out += [(("mlp", "w_gate"), (D, F), 0.0, 1 / math.sqrt(D), True)]
    out += [(("mlp", "w_up"), (D, F), 0.0, 1 / math.sqrt(D), True),
            (("mlp", "w_down"), (F, D), 0.0, 1 / math.sqrt(F), True)]
    return out


def _draw(key, shape, mean: float, std: float, dtype):
    """Uniform values of the given mean and standard deviation."""
    a = std * math.sqrt(3.0)
    x = jax.random.uniform(key, shape, jnp.float32, -a, a) + mean
    return x.astype(dtype)


def _layer(cfg: dict, key, li, param_dtype, as_dtype=None) -> dict:
    lkey = jax.random.fold_in(key, li)
    out: dict = {}
    for n, (path, shape, mean, std, in_pdt) in enumerate(layer_leaves(cfg)):
        dt = param_dtype if in_pdt else jnp.float32
        x = _draw(jax.random.fold_in(lkey, n), shape, mean, std, dt)
        if as_dtype is not None:
            x = x.astype(as_dtype)
        out.setdefault(path[0], {})[path[1]] = x
    return out


def _top(cfg: dict, key, param_dtype, as_dtype=None) -> dict:
    D, _, _, _, _, V = _dims(cfg)
    tkey = jax.random.fold_in(key, 1 << 20)
    cast = (lambda x: x) if as_dtype is None else (lambda x: x.astype(as_dtype))
    out = {"embed": cast(_draw(jax.random.fold_in(tkey, 0), (V, D), 0.0,
                                0.02, param_dtype)),
           "final_norm": {}}
    for n, leaf in enumerate(_norm_leaves(cfg)):
        mean = 1.0 if leaf == "scale" else 0.0
        out["final_norm"][leaf] = cast(_draw(jax.random.fold_in(tkey, 1 + n),
                                             (D,), mean, 0.1, jnp.float32))
    if not cfg.get("tie_embeddings"):
        out["lm_head"] = cast(_draw(jax.random.fold_in(tkey, 9), (D, V), 0.0,
                                    0.02, param_dtype))
    return out


def make_params(cfg: dict, seed: int) -> dict:
    """The served weights: ``{"embed", "layers", "final_norm",
    "lm_head"}`` with the layer weights stacked on a leading axis, in the
    configuration's ``param_dtype`` (norms in float32), built on the
    device in one jitted call."""
    pdt = _DTYPES[cfg["param_dtype"]]

    @jax.jit
    def build(key):
        layers = jax.lax.map(lambda li: _layer(cfg, key, li, pdt),
                             jnp.arange(cfg["n_layers"], dtype=jnp.uint32))
        return {**_top(cfg, key, pdt), "layers": layers}

    return build(base_key(seed))


# -- the plain reference ----------------------------------------------------

def _norm(x, p, kind: str):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
            * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]


def _rope(x, theta: float):
    """x: (N, T, heads, hd); rotates the (even, odd) pairs of each head by
    the angle position / theta ** (2i / hd)."""
    T, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _block(cfg: dict, x, p):
    """One decoder layer over ``x: (N, T, D)`` in float32."""
    _, H, K, hd, _, _ = _dims(cfg)
    N, T, _ = x.shape
    h = _norm(x, p["ln1"], cfg["norm"])
    q, k, v = h @ p["attn"]["wq"], h @ p["attn"]["wk"], h @ p["attn"]["wv"]
    if "bq" in p["attn"]:
        q, k, v = q + p["attn"]["bq"], k + p["attn"]["bk"], v + p["attn"]["bv"]
    q, k, v = (q.reshape(N, T, H, hd), k.reshape(N, T, K, hd),
               v.reshape(N, T, K, hd))
    if cfg.get("rope", True):
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    G = H // K
    q = q.reshape(N, T, K, G, hd)
    s = jnp.einsum("ntkgh,nskh->nkgts", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("nkgts,nskh->ntkgh", jax.nn.softmax(s, -1), v)
    x = x + o.reshape(N, T, H * hd) @ p["attn"]["wo"]
    h = _norm(x, p["ln2"], cfg["norm"])
    if cfg["act"] in ("swiglu", "geglu"):
        g = h @ p["mlp"]["w_gate"]
        g = jax.nn.silu(g) if cfg["act"] == "swiglu" else \
            jax.nn.gelu(g, approximate=True)
        h = g * (h @ p["mlp"]["w_up"])
    else:
        h = jax.nn.gelu(h @ p["mlp"]["w_up"], approximate=True)
    return x + h @ p["mlp"]["w_down"]


def _fp8_encode(p: dict):
    """Every matrix as float8 (e4m3) values and one scale per column."""
    def enc(w):
        w = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return (w / scale).astype(jnp.float8_e4m3fn), scale
    return jax.tree_util.tree_map(lambda w: enc(w) if w.ndim >= 2 else w, p)


def _fp8_decode(q):
    return jax.tree_util.tree_map(
        lambda x: x[0].astype(jnp.float32) * x[1] if isinstance(x, tuple)
        else x.astype(jnp.float32), q,
        is_leaf=lambda x: isinstance(x, tuple))


_fp8_encode_jit = jax.jit(_fp8_encode)
_fp8_decode_jit = jax.jit(_fp8_decode)


def fp8_weights(p: dict) -> dict:
    """The control's weights: every matrix rounded to float8 (e4m3) with
    one scale per output column, and back to float32.  Encoding and
    decoding are separate programs, so that the compiler cannot drop the
    rounding as excess precision."""
    return _fp8_decode_jit(_fp8_encode_jit(p))


def reference(cfg: dict, seed: int, sequences: list[list[int]],
              starts: list[int], control: bool = False,
              block_bytes: int = 1 << 30) -> dict:
    """Greedy gaps of served tokens under the float32 reference.

    ``sequences[i]`` is a prompt followed by its served tokens, and
    ``starts[i]`` the position of the first served token.  For every
    served token the gap is the reference's best logit at its position
    less the reference's logit of the served token; ``gap`` is the widest.
    With ``control`` the same forward pass runs beside it with fp8
    weights, and ``control_gap`` is the widest gap, under the reference,
    of the token that the fp8 pass puts first at each of those
    positions; and once more with the reference's weights but matmuls
    of one bfloat16 pass, whose gap read the same way is
    ``witness_gap``: what rounding to bfloat16 alone does to the logits.

    The pass runs layer by layer, each layer over blocks of sequences
    whose attention scores take about ``block_bytes``, so that long
    sequences fit."""
    pdt = _DTYPES[cfg["param_dtype"]]
    _, H, _, _, _, _ = _dims(cfg)
    key = base_key(seed)
    T = max(len(s) for s in sequences)
    per_block = min(len(sequences), max(1, block_bytes // (4 * H * T * T)))
    N = -(-len(sequences) // per_block) * per_block
    toks = np.zeros((N, T), np.int32)
    for i, s in enumerate(sequences):
        toks[i, :len(s)] = s
    rows, cols, want = [], [], []
    for i, (s, st) in enumerate(zip(sequences, starts)):
        for t in range(st, len(s)):
            rows.append(i)
            cols.append(t - 1)
            want.append(s[t])
    rows, cols, want = (jnp.asarray(rows), jnp.asarray(cols),
                        jnp.asarray(want))
    with jax.default_matmul_precision("highest"):
        top = jax.jit(functools.partial(_top, cfg, param_dtype=pdt,
                                        as_dtype=jnp.float32))(key)
        layer = jax.jit(functools.partial(_layer, cfg, param_dtype=pdt,
                                          as_dtype=jnp.float32))

        def blocks(x, p):
            return jax.lax.map(lambda xb: _block(cfg, xb, p), x)

        full = jax.jit(blocks)
        with jax.default_matmul_precision("bfloat16"):
            low = jax.jit(blocks)
        quant = fp8_weights
        shape = (N // per_block, per_block, T)
        x = jnp.take(top["embed"], jnp.asarray(toks.reshape(shape)), axis=0)
        xc = jnp.take(quant(top)["embed"], jnp.asarray(toks.reshape(shape)),
                      axis=0) if control else None
        xw = x if control else None
        for li in range(cfg["n_layers"]):
            p = layer(key, jnp.uint32(li))
            x = full(x, p)
            if control:
                xc = full(xc, quant(p))
                with jax.default_matmul_precision("bfloat16"):
                    xw = low(xw, p)
            del p

        @jax.jit
        def logits_at(x, top):
            x = x.reshape(N, T, -1)
            h = _norm(x[rows, cols], top["final_norm"], cfg["norm"])
            head = top["embed"].T if cfg.get("tie_embeddings") \
                else top["lm_head"]
            return h @ head

        ref = logits_at(x, top)
        best = ref.max(-1)
        gaps = best - jnp.take_along_axis(ref, want[:, None], -1)[:, 0]
        out = {"gap": float(gaps.max()), "tokens": int(want.shape[0])}
        if control:
            ctl = logits_at(xc, quant(top))
            pick = jnp.argmax(ctl, -1)
            cg = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            out["control_gap"] = float(cg.max())
            pick = jnp.argmax(logits_at(xw, top), -1)
            wg = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            out["witness_gap"] = float(wg.max())
    return out
