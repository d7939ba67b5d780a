"""Seeded model weights, made on the device in one jitted call.

The benchmark owns its weights: the program under test receives them, and
the plain reference (``bench/reference.py``) builds the same values again
from the same seed once the program's state is freed, so the comparison
takes nothing that the program made.

The tree follows the layout the serving engine reads (``repro.models``):
per-layer leaves are stacked over a leading layer axis, norm weights are
stored as an offset from 1 (the model computes ``x * (1 + w)``), and the
embedding table is padded to a multiple of 2048 rows. Rows past the
published vocabulary are zero, so no padded id is ever the largest logit.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

VOCAB_PAD = 2048
NORM_STD = 0.1     # spread of the stored norm offsets around 1
EMBED_STD = 0.02   # embedding table and router, as the program's own init


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_PAD) * VOCAB_PAD


def specs(model: Dict) -> Dict[Tuple[str, ...], Tuple[Tuple[int, ...], float]]:
    """``{path: (shape, std)}`` for every leaf of the model's weights.

    ``model`` is the ``model`` block of a configuration file (published key
    names). Matrices take a normal draw scaled by ``1/sqrt(fan_in)``."""
    L = model["num_hidden_layers"]
    d = model["hidden_size"]
    hd = model["head_dim"]
    H, Hkv = model["num_attention_heads"], model["num_key_value_heads"]
    ff = model["intermediate_size"]
    E = model.get("num_local_experts", 0)
    out = {("embed", "table"): ((padded_vocab(model["vocab_size"]), d), EMBED_STD),
           ("final_norm",): ((d,), NORM_STD)}
    blk = ("groups", "b0")
    out[blk + ("norm1",)] = ((L, d), NORM_STD)
    out[blk + ("norm2",)] = ((L, d), NORM_STD)
    for name, shape in (("wq", (d, H * hd)), ("wk", (d, Hkv * hd)),
                        ("wv", (d, Hkv * hd)), ("wo", (H * hd, d))):
        out[blk + ("attn", name)] = ((L,) + shape, 1 / math.sqrt(shape[0]))
    if E:
        out[blk + ("moe", "router")] = ((L, d, E), EMBED_STD)
        for name, shape in (("w_gate", (E, d, ff)), ("w_up", (E, d, ff)),
                            ("w_down", (E, ff, d))):
            out[blk + ("moe", name)] = ((L,) + shape, 1 / math.sqrt(shape[1]))
    else:
        for name, shape in (("w_gate", (d, ff)), ("w_up", (d, ff)),
                            ("w_down", (ff, d))):
            out[blk + ("mlp", name)] = ((L,) + shape, 1 / math.sqrt(shape[0]))
    return out


def _nest(flat: Dict[Tuple[str, ...], jax.Array]) -> Dict:
    tree: Dict = {}
    for path, leaf in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return tree


@functools.partial(jax.jit, static_argnames=("layout", "vocab", "dtype"))
def _make(key, layout, vocab: int, dtype: str):
    flat = {}
    for i, (path, shape, std) in enumerate(layout):
        x = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        if path == ("embed", "table"):
            x = jnp.where(jnp.arange(shape[0])[:, None] < vocab, x, 0.0)
        flat[path] = x.astype(dtype)
    return _nest(flat)


def make(model: Dict, seed: int, dtype: str = "bfloat16") -> Dict:
    """The weights for ``seed``, as one device-resident tree of ``dtype``."""
    layout = tuple((p, s, std) for p, (s, std) in sorted(specs(model).items()))
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return _make(key, layout, model["vocab_size"], dtype)
