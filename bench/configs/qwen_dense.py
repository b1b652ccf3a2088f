"""Qwen1.5 (Qwen2 architecture) dense decoder: the benchmark's weights and its
plain float32 reference.

Published description (Hugging Face ``Qwen2ForCausalLM``): token embedding;
per layer a pre-RMSNorm self attention with biased q/k/v projections, rotary
embeddings on q and k (rotate-half form), grouped-query heads and an unbiased
output projection, then a pre-RMSNorm SwiGLU MLP (``down(silu(gate) * up)``),
each with a residual; a final RMSNorm and the LM head (the transposed
embedding where ``tie_word_embeddings`` is set).

The reference imports nothing of the program under test.  ``make_params``
builds the weights in the param-tree layout the engine consumes
(``embed``, ``blocks[i].{ln1, mixer, ln2, mlp}``, ``final_norm``, ``lm_head``),
so the program and the reference read the same arrays, which the benchmark
made from the seed.  The reference runs layer by layer and, for the MLP, in
slices of the intermediate width, upcasting one slice of weights at a time,
so that it fits beside the weights on one chip.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0

# rows of queries per attention block and per LM-head block; columns of the
# intermediate width per MLP slice: bounds the reference's float32
# temporaries (scores, logits, upcast weights) to a few hundred MB
Q_BLOCK = 512
HEAD_ROWS = 256
MLP_SLICE = 8192


def dims(c: dict) -> dict:
    """The sizes a run needs, from a configuration file's keys."""
    H = c["num_attention_heads"]
    d = c["hidden_size"]
    return {"d": d, "H": H, "Kh": c["num_key_value_heads"],
            "hd": c.get("head_dim") or d // H, "ff": c["intermediate_size"],
            "L": c["num_hidden_layers"], "V": c["vocab_size"],
            "tied": bool(c["tie_word_embeddings"]),
            "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"])}


def program_config(c: dict) -> dict:
    """Keyword arguments of the program's ``ModelConfig`` for this file."""
    m = dims(c)
    return {"name": c["name"], "family": "dense", "n_layers": m["L"],
            "d_model": m["d"], "n_heads": m["H"], "n_kv_heads": m["Kh"],
            "d_ff": m["ff"], "vocab_size": m["V"], "head_dim": m["hd"],
            "qkv_bias": True, "rope_theta": m["theta"], "rms_eps": m["eps"],
            "tie_embeddings": m["tied"], "source": c["source"]}


# ---------------------------------------------------------------------------
# Weights, made on the device from the seed in one jitted call
# ---------------------------------------------------------------------------

def _layer(key, m: dict, dtype):
    d, H, Kh, hd, ff, L = m["d"], m["H"], m["Kh"], m["hd"], m["ff"], m["L"]
    k = jax.random.split(key, 12)
    s = 1.0 / math.sqrt(d)
    so = s / math.sqrt(2 * L)
    sd = 1.0 / math.sqrt(ff) / math.sqrt(2 * L)

    def n(i, shape, scale):
        return (jax.random.normal(k[i], shape, f32) * scale).astype(dtype)

    return {
        "ln1": {"scale": (1.0 + n(0, (d,), 0.1)).astype(dtype)},
        "mixer": {"wq": n(1, (d, H, hd), s), "wk": n(2, (d, Kh, hd), s),
                  "wv": n(3, (d, Kh, hd), s), "wo": n(4, (H, hd, d), so),
                  "bq": n(5, (H, hd), 0.5), "bk": n(6, (Kh, hd), 0.5),
                  "bv": n(7, (Kh, hd), 0.5)},
        "ln2": {"scale": (1.0 + n(8, (d,), 0.1)).astype(dtype)},
        "mlp": {"w_gate": n(9, (d, ff), s), "w_up": n(10, (d, ff), s),
                "w_down": n(11, (ff, d), sd)},
    }


def make_params(c: dict, seed_key, dtype=jnp.bfloat16):
    """All weights from ``seed_key`` in ``dtype``, made on the device in one
    jitted call.  Layer i draws from ``fold_in(key, i)``."""
    m = dims(c)

    @jax.jit
    def build(key):
        ke, kh, kn = jax.random.split(jax.random.fold_in(key, 1_000_003), 3)
        s = 1.0 / math.sqrt(m["d"])
        p = {"embed": (jax.random.normal(ke, (m["V"], m["d"]), f32)
                       * s).astype(dtype),
             "final_norm": {"scale": (1.0 + 0.1 * jax.random.normal(
                 kn, (m["d"],), f32)).astype(dtype)},
             "blocks": [_layer(jax.random.fold_in(key, i), m, dtype)
                        for i in range(m["L"])]}
        if not m["tied"]:
            p["lm_head"] = (jax.random.normal(kh, (m["d"], m["V"]), f32)
                            * s).astype(dtype)
        return p

    return build(seed_key)


# ---------------------------------------------------------------------------
# The reference, and the control: the same model with fp8 weights
# ---------------------------------------------------------------------------

def _fp8(w, contract):
    """Round ``w`` to float8 e4m3 with one absmax scale per output channel
    (the maximum over the ``contract`` axes), as an fp8 weight-only
    deployment stores it, and return it in float32."""
    w = w.astype(f32)
    scale = jnp.max(jnp.abs(w), axis=contract, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (w / scale).astype(FP8).astype(f32) * scale


def _w(w, fp8: bool, contract=(0,)):
    return _fp8(w, contract) if fp8 else w.astype(f32)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(f32)


def _rope(x, pos, theta):
    """Rotate-half rotary embedding: x (T, heads, hd), pos (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd))
    ang = pos.astype(f32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@partial(jax.jit, static_argnames=("theta", "eps", "fp8"))
def _attention(x, p, *, theta, eps, fp8):
    """x (T, d) float32 -> x + causal self attention of x.  Padding rows at
    the end only ever feed later rows, so they change no valid row."""
    T = x.shape[0]
    h = _rms(x, p["ln1"]["scale"], eps)
    a = p["mixer"]
    q = jnp.einsum("td,dhk->thk", h, _w(a["wq"], fp8),
                   precision=HIGHEST) + a["bq"].astype(f32)
    k = jnp.einsum("td,dhk->thk", h, _w(a["wk"], fp8),
                   precision=HIGHEST) + a["bk"].astype(f32)
    v = jnp.einsum("td,dhk->thk", h, _w(a["wv"], fp8),
                   precision=HIGHEST) + a["bv"].astype(f32)
    pos = jnp.arange(T)
    q = _rope(q, pos, theta)
    k = _rope(k, pos, theta)
    H, Kh, hd = q.shape[1], k.shape[1], q.shape[2]
    G = H // Kh
    kx = jnp.repeat(k, G, axis=1)                    # (T, H, hd)
    vx = jnp.repeat(v, G, axis=1)
    outs = []
    for r0 in range(0, T, Q_BLOCK):
        qb = q[r0:r0 + Q_BLOCK] / math.sqrt(hd)
        s = jnp.einsum("qhk,thk->hqt", qb, kx, precision=HIGHEST)
        qpos = r0 + jnp.arange(qb.shape[0])
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqt,thk->qhk", w, vx, precision=HIGHEST))
    o = jnp.concatenate(outs, axis=0)
    y = jnp.einsum("thk,hkd->td", o, _w(a["wo"], fp8, (0, 1)),
                   precision=HIGHEST)
    return x + y


@partial(jax.jit, static_argnames=("eps", "fp8"))
def _mlp_slice(x, ln2, wg, wu, wd, *, eps, fp8):
    """One slice of the intermediate width's contribution to the MLP."""
    h = _rms(x, ln2, eps)
    g = jnp.dot(h, _w(wg, fp8), precision=HIGHEST)
    u = jnp.dot(h, _w(wu, fp8), precision=HIGHEST)
    return jnp.dot(jax.nn.silu(g) * u, _w(wd, fp8), precision=HIGHEST)


@partial(jax.jit, static_argnames=("eps", "fp8"))
def _head_rows(x, norm, w, picks, *, eps, fp8):
    """Logit statistics for a block of rows: the best logit, the argmax, and
    the logits of ``picks`` (rows, k) token ids."""
    h = _rms(x, norm, eps)
    logits = jnp.dot(h, _w(w, fp8), precision=HIGHEST)     # (R, V)
    return (logits.max(axis=-1), jnp.argmax(logits, axis=-1).astype(jnp.int32),
            jnp.take_along_axis(logits, picks, axis=1))


def _bucket(n: int) -> int:
    b = 128
    while b < n:
        b *= 2
    return b


def logit_stats(c: dict, params, tokens: np.ndarray, picks: np.ndarray,
                fp8: bool = False):
    """Teacher-forced pass over ``tokens`` (T,).

    Returns, for every position t, the best logit, its token id, and the
    logits of ``picks[t]`` (T, k) — the token ids whose standing against the
    best is asked for.  ``fp8`` runs the control: every matmul weight rounded
    to float8 e4m3 with per-channel scales, everything else as the reference.
    """
    m = dims(c)
    T = len(tokens)
    Tp = _bucket(T)
    tok = np.zeros(Tp, np.int32)
    tok[:T] = tokens
    pk = np.zeros((Tp, picks.shape[1]), np.int32)
    pk[:T] = picks
    x = params["embed"][jnp.asarray(tok)].astype(f32)
    for bp in params["blocks"]:
        x = _attention(x, bp, theta=m["theta"], eps=m["eps"], fp8=fp8)
        mlp = bp["mlp"]
        y = jnp.zeros_like(x)
        for c0 in range(0, m["ff"], MLP_SLICE):
            sl = slice(c0, c0 + MLP_SLICE)
            y = y + _mlp_slice(x, bp["ln2"]["scale"], mlp["w_gate"][:, sl],
                               mlp["w_up"][:, sl], mlp["w_down"][sl],
                               eps=m["eps"], fp8=fp8)
        x = x + y
    w = params["embed"].T if m["tied"] else params["lm_head"]
    best, arg, got = [], [], []
    for r0 in range(0, Tp, HEAD_ROWS):
        b, a, g = _head_rows(x[r0:r0 + HEAD_ROWS],
                             params["final_norm"]["scale"], w,
                             jnp.asarray(pk[r0:r0 + HEAD_ROWS]),
                             eps=m["eps"], fp8=fp8)
        best.append(np.asarray(b))
        arg.append(np.asarray(a))
        got.append(np.asarray(g))
    return (np.concatenate(best)[:T], np.concatenate(arg)[:T],
            np.concatenate(got)[:T])
