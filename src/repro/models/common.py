"""Shared layers: param leaves, initializers, norms, RoPE variants, embeddings.

No flax — params are plain pytrees.  Each leaf is created through ``param``,
which records its logical sharding axes in a parallel tree (see
``split_params``): model code stays a pure function of (params, inputs).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ParamLeaf:
    """An array tagged with logical sharding axes; flattens to the array."""

    value: jnp.ndarray
    axes: Tuple[Optional[str], ...]

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, axes, children):
        return cls(children[0], axes)


def param(key, shape, axes, scale: Optional[float] = None, dtype=jnp.float32):
    """Truncated-normal init with fan-in scaling (scale=None) or constant std."""
    if scale is None:
        fan_in = shape[0] if len(shape) >= 1 else 1
        scale = 1.0 / np.sqrt(max(1, fan_in))
    init = scale * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return ParamLeaf(init.astype(dtype), tuple(axes))


def zeros_param(shape, axes, dtype=jnp.float32):
    return ParamLeaf(jnp.zeros(shape, dtype), tuple(axes))


def ones_param(shape, axes, dtype=jnp.float32):
    return ParamLeaf(jnp.ones(shape, dtype), tuple(axes))


def const_param(value, axes):
    return ParamLeaf(jnp.asarray(value), tuple(axes))


def is_param(x) -> bool:
    return isinstance(x, ParamLeaf)


def split_params(tree):
    """(ParamLeaf tree) -> (values tree, logical-axes tree)."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=is_param)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=is_param)
    return values, axes


def merge_params(values, axes):
    return jax.tree.map(lambda v, a: ParamLeaf(v, a), values, axes,
                        is_leaf=lambda x: isinstance(x, tuple) and not isinstance(x, list))


def value_of(p):
    return p.value if isinstance(p, ParamLeaf) else p


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float = 1e-6):
    scale = value_of(scale)
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def soft_cap(x, cap: float):
    if cap and cap > 0.0:
        return cap * jnp.tanh(x / cap)
    return x


# ---------------------------------------------------------------------------
# rotary position embeddings (default / partial / M-RoPE)
# ---------------------------------------------------------------------------

def _rope_angles(positions, dim: int, theta: float):
    """positions [..., S] -> cos/sin [..., S, dim/2] (fp32)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, positions, theta: float = 10_000.0, fraction: float = 1.0):
    """x [B,S,H,D]; positions [B,S].  ``fraction`` < 1 rotates only the first
    ``fraction*D`` dims (chatglm-style partial / "2d" rope)."""
    d = x.shape[-1]
    rot = int(d * fraction)
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    cos, sin = _rope_angles(positions, rot, theta)  # [B,S,rot/2]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


def apply_mrope(x, positions_thw, sections: Tuple[int, int, int], theta: float):
    """Qwen2-VL multimodal RoPE.

    x [B,S,H,D]; positions_thw [3,B,S] (temporal, height, width ids).  The
    D/2 frequency slots are split into ``sections`` (t,h,w); each section
    takes its angle from the corresponding position component.  For pure-text
    tokens the three ids are equal, reducing to standard RoPE.
    """
    d = x.shape[-1]
    half = d // 2
    secs = np.array(sections, dtype=np.int64)
    secs = (secs * half // secs.sum()).tolist()
    secs[-1] = half - sum(secs[:-1])
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    # select which position component feeds each frequency slot
    comp = jnp.repeat(jnp.arange(3), jnp.array(secs), total_repeat_length=half)  # [half]
    onehot = jax.nn.one_hot(comp, 3, dtype=jnp.float32)  # [half,3]
    # pos_for_slot [B,S,half] = sum_c onehot[half,c] * positions[c,B,S]
    pos_slot = jnp.einsum("kc,cbs->bsk", onehot, positions_thw.astype(jnp.float32))
    ang = pos_slot * freqs[None, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rope_for(cfg, x, positions, local: bool):
    """Dispatch on cfg.rope_kind.  ``positions`` is [B,S] or [3,B,S] (mrope)."""
    if cfg.rope_kind == "none":
        return x
    if cfg.rope_kind == "mrope":
        if positions.ndim == 2:  # text-only fallback: t=h=w
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return apply_mrope(x, positions, cfg.mrope_sections, cfg.rope_theta)
    theta = cfg.rope_theta_local if local else cfg.rope_theta
    frac = cfg.rope_fraction if cfg.rope_kind == "partial" else 1.0
    return apply_rope(x, positions, theta, frac)


def sinusoidal_positions(seq: int, dim: int):
    pos = np.arange(seq)[:, None]
    i = np.arange(dim // 2)[None, :]
    ang = pos / np.power(10_000.0, 2 * i / dim)
    return jnp.asarray(
        np.concatenate([np.sin(ang), np.cos(ang)], axis=-1), dtype=jnp.float32
    )


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, cfg):
    return {
        "table": param(
            key, (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"), scale=0.02
        )
    }


def embed(params, ids, cfg):
    table = value_of(params["table"]).astype(cfg.compute_dtype)
    x = jnp.take(table, ids, axis=0)
    return x * jnp.sqrt(jnp.asarray(cfg.d_model, x.dtype))


def unembed(params, x, cfg, table=None):
    """Logits via the (tied) embedding table or a dedicated head."""
    t = value_of(table if table is not None else params["table"])
    return jnp.einsum("bsd,vd->bsv", x, t.astype(x.dtype))


def at_layer(stack, layer):
    """Layer ``layer`` of every leaf of a tree stacked over layers."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
        stack)
