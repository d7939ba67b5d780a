"""Plain reference of the served models, and its lower-precision control.

A full causal forward pass over each whole sequence in float32, with
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching of slots, no expert capacity. It imports nothing of the program;
its weights come from ``bench/weights.py`` for the same seed.

The equations are the model as the repository defines it. Where that
departs from the published model (the embedding scale, the norm epsilon,
Granite's four scalar multipliers), the configuration file's ``as_run``
block states the value the program runs with, and the reference takes it
from there; ``PERF.md`` lists each departure.

Two things are compared with it: the logits of the tokens the program
served (``logits``), and the keys and values the program cached for the
requests still in flight (``kv_gap``).

The control is the same forward pass one step below the bfloat16 that
the program serves in: float8 (e4m3) matrix products, with every weight
matrix rounded to float8 with one scale per output channel and every
activation row entering a product rounded to float8 with its own scale,
and bfloat16 elsewhere.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, w, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def _rope(x, theta):
    """x [n, T, heads, hd]; rotates the two halves of each head."""
    T, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


E4M3_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)


def _fp8(a, axis):
    """``a`` rounded to float8 (e4m3), scaled along ``axis`` so that the
    largest entry of each slice maps to e4m3's largest value."""
    f = a.astype(jnp.float32)
    scale = jnp.max(jnp.abs(f), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (f / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _fp8_rows(x):
    """Each row of ``x`` rounded to float8 with its own scale."""
    return _fp8(x, -1).astype(x.dtype)


def _mm(x, w, low: bool, spec: str = "...d,df->...f"):
    """A matrix product; the control rounds its activation rows first."""
    return jnp.einsum(spec, _fp8_rows(x) if low else x, w)


def _ffn(h, w, low):
    return _mm(jax.nn.silu(_mm(h, w["w_gate"], low))
               * _mm(h, w["w_up"], low), w["w_down"], low)


def _moe(h, w, top_k, low):
    """Every token through its ``top_k`` experts, gates renormalised."""
    probs = jax.nn.softmax(_mm(h, w["router"], low).astype(jnp.float32), -1)
    top, ids = jax.lax.top_k(probs, top_k)
    top = top / top.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs)
    gates = jax.vmap(jax.vmap(lambda g, i, t: g.at[i].set(t)))(gates, ids, top)
    hid = jax.nn.silu(_mm(h, w["w_gate"], low, "ntd,edf->ntef"))
    hid = hid * _mm(h, w["w_up"], low, "ntd,edf->ntef")
    out = _mm(hid, w["w_down"], low, "ntef,efd->nted")
    return jnp.einsum("nted,nte->ntd", out, gates.astype(h.dtype))


@functools.partial(jax.jit, static_argnames=("model", "low"))
def _layer(x, w, model, low):
    m = dict(model)
    n, T, _ = x.shape
    H, Hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    h = _rms(x, w["norm1"], m["rms_norm_eps"])
    a = w["attn"]
    q = _rope(_mm(h, a["wq"], low).reshape(n, T, H, hd), m["rope_theta"])
    k = _rope(_mm(h, a["wk"], low).reshape(n, T, Hkv, hd), m["rope_theta"])
    v = _mm(h, a["wv"], low).reshape(n, T, Hkv, hd)
    kv = (k, v)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k).astype(jnp.float32) * m.get(
        "attention_multiplier", 1 / math.sqrt(hd))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1).astype(v.dtype), v)
    r = m.get("residual_multiplier", 1.0)
    x = x + r * _mm(o.reshape(n, T, H * hd), a["wo"], low)
    h = _rms(x, w["norm2"], m["rms_norm_eps"])
    if "moe" in w:
        return x + r * _moe(h, w["moe"], m["num_experts_per_tok"], low), kv
    return x + r * _ffn(h, w["mlp"], low), kv


@functools.partial(jax.jit, static_argnames=("model", "low"))
def _head(x, final_norm, table, pick, model, low):
    m = dict(model)
    h = _rms(x, final_norm, m["rms_norm_eps"])
    h = jnp.take_along_axis(h, pick[..., None], axis=1)
    out = _mm(h, table[: m["vocab_size"]].T, low)
    return out.astype(jnp.float32) / m.get("logits_scaling", 1.0)


def fp8_weights(weights: Dict) -> Dict:
    """Every matrix rounded to float8 (e4m3) with one scale per output
    channel (the last axis; the embedding table's rows, which are also its
    output channels as the tied head). Vectors stay as they are."""
    def q(path, a):
        if a.ndim < 2 + (path[0].key == "groups"):
            return a.astype(jnp.float32)
        return _fp8(a, -1 if path[-1].key == "table" else -2)
    return jax.tree_util.tree_map_with_path(q, weights)


def _layers(weights: Dict, model: Dict, tokens: np.ndarray, control: bool):
    """The forward pass, layer by layer, in the reference's precision (or
    the control's): yields ``(x, (k, v))`` after each layer, with the keys
    (after RoPE) and values its attention read, each ``[n, T, kv_heads,
    head_dim]``, and at the end ``(logits_fn, None)``, where
    ``logits_fn(pick)`` gives the head's logits at positions ``pick``."""
    m = tuple(sorted((k, v) for k, v in model.items()
                     if isinstance(v, (int, float))))
    dtype = "bfloat16" if control else "float32"
    w = jax.tree.map(lambda a: a.astype(dtype),
                     fp8_weights(weights) if control else weights)
    table = w["embed"]["table"]
    precision = "default" if control else "highest"
    x = (table[jnp.asarray(tokens)].astype(jnp.float32)
         * model["embedding_multiplier"]).astype(dtype)
    blk = w["groups"]["b0"]
    for layer in range(model["num_hidden_layers"]):
        with jax.default_matmul_precision(precision):
            x, kv = _layer(x, jax.tree.map(lambda a: a[layer], blk),
                           model=m, low=control)
        yield x, kv

    def head(pick):
        with jax.default_matmul_precision(precision):
            return _head(x, w["final_norm"], table, jnp.asarray(pick),
                         model=m, low=control)
    yield head, None


def logits(weights: Dict, model: Dict, tokens: np.ndarray,
           pick: np.ndarray, control: bool = False) -> jax.Array:
    """``[n, K, vocab]`` float32 logits at positions ``pick [n, K]`` of the
    sequences ``tokens [n, T]`` (positions past a sequence's end are
    padding, which a causal pass never lets back into earlier ones).
    ``control`` runs the float8 control in place of the reference."""
    for head, _ in _layers(weights, model, tokens, control):
        pass
    return head(pick)


def kv_gap(weights: Dict, model: Dict, tokens: np.ndarray,
           lengths: np.ndarray, keys: np.ndarray, values: np.ndarray,
           control: bool = False) -> float:
    """How far cached ``keys`` and ``values`` ``[L, n, T, kv_heads,
    head_dim]`` lie from the float32 reference's for the sequences
    ``tokens [n, T]`` (lanes of length 0 are padding): at each position
    below a lane's length the relative distance ``|a - r| / |r|`` over
    every layer, head and dimension; the median of these over the lane's
    positions; the largest over lanes; the larger of the keys' and the
    values'. The median, so that a near tie in expert routing that
    bfloat16 breaks the other way, which moves one position a lot and
    the rest little, does not count, while a lower precision, which moves
    every position, does; the largest over lanes, so that one lane left
    stale reads about 1. ``control`` measures the float8 control's own
    keys and values in place of the cached ones."""
    ref = _layers(weights, model, tokens, False)
    low = _layers(weights, model, tokens, True) if control else None
    n, T = tokens.shape
    num = np.zeros((2, n, T))
    den = np.zeros((2, n, T))
    for layer in range(model["num_hidden_layers"]):
        _, want = next(ref)
        got = next(low)[1] if control else (keys[layer], values[layer])
        for i, (r, a) in enumerate(zip(want, got)):
            r = np.asarray(r, np.float32)
            a = np.asarray(a, np.float32)
            num[i] += ((a - r) ** 2).sum((-2, -1))
            den[i] += (r ** 2).sum((-2, -1))
    gap = np.sqrt(num / np.maximum(den, np.finfo(np.float32).tiny))
    return float(max(np.median(gap[i, lane, :lengths[lane]])
                     for i in range(2) for lane in range(n)
                     if lengths[lane] > 0))
