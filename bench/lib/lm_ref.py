"""Weights from the seed, and a plain float32 reference of a Llama-style
decoder (H2O-Danube3), independent of the program's model code.

``make_weights`` builds the weights in the layout the program's serving
path takes (the benchmark's only knowledge of the program's model is that
layout), in bfloat16, on the device, in one jitted call.  ``logits`` is the
published architecture written out plainly: RMSNorm, rotary positions
(rotate-half pairs, as Llama), grouped-query causal attention, SwiGLU,
untied head, float32 at ``highest`` matmul precision, one layer at a time
so that it fits beside the bfloat16 weights.  The program stores each norm
weight as ``1 + scale``; the reference reads it so.

``quant="fp8"`` is the control: every linear layer's weight (per output
channel) and input (per row) rounded to float8_e4m3 with absmax scaling,
the step to lower precision that the comparison must reject.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16


@dataclass(frozen=True)
class Arch:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_eps: float
    init_std: float
    norm_std: float

    @property
    def vocab_padded(self) -> int:
        return -(-self.vocab // 256) * 256

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                   vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                   rms_eps=float(c["rms_norm_eps"]),
                   init_std=float(c["initializer_range"]),
                   norm_std=float(c["norm_weight_std"]))


def weight_shapes(a: Arch) -> dict:
    """The program's parameter layout for a uniform full-attention dense
    decoder (one layer group per layer, stacked on the leading axis), as
    ``{path: shape}``."""
    n, d, f, hd = a.n_layers, a.d_model, a.d_ff, a.head_dim
    g = "groups/c0/"
    return {
        "embed/w": (a.vocab_padded, d),
        "final_norm/scale": (d,),
        "lm_head/w": (a.vocab_padded, d),
        g + "norm1/scale": (n, d),
        g + "attn/wq": (n, d, a.n_heads * hd),
        g + "attn/wk": (n, d, a.n_kv_heads * hd),
        g + "attn/wv": (n, d, a.n_kv_heads * hd),
        g + "attn/wo": (n, a.n_heads * hd, d),
        g + "norm2/scale": (n, d),
        g + "mlp/w_gate": (n, d, f),
        g + "mlp/w_up": (n, d, f),
        g + "mlp/w_down": (n, f, d),
    }


def make_weights(a: Arch, key_seed: int):
    """Normal weights (std ``init_std``; norm scales std ``norm_std``) in
    bfloat16, made on the device in one jitted call.  The norm scales come
    from a fixed key, the same for every seed: the program compiles arrays
    under 1 MiB into its programs as constants, so seed-dependent norm
    scales would make every seed compile anew."""
    shapes = weight_shapes(a)

    def make(key):
        params: dict = {"rest": ()}
        fixed = jax.random.PRNGKey(0)
        for i, (path, shape) in enumerate(shapes.items()):
            norm = path.endswith("scale")
            std = a.norm_std if norm else a.init_std
            x = jax.random.normal(
                jax.random.fold_in(fixed if norm else key, i), shape, F32)
            *parents, leaf = path.split("/")
            node = params
            for name in parents:
                node = node.setdefault(name, {})
            node[leaf] = (x * std).astype(BF16)
        return params

    return jax.jit(make)(jax.random.PRNGKey(key_seed))


# --------------------------------------------------------------------- #
# The reference forward.
# --------------------------------------------------------------------- #
def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _linear(x, w, quant):
    """x (..., in) @ w (in, out) in float32, or through fp8 for the
    control."""
    w = w.astype(F32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rmsnorm(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + scale.astype(F32))


def _rope(x, theta):
    """x: (B, T, H, hd); rotate-half pairs (i, i + hd/2)."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("a", "quant"))
def _layer(x, p, a: Arch, quant):
    with jax.default_matmul_precision("highest"):
        B, T, _ = x.shape
        H, K, hd = a.n_heads, a.n_kv_heads, a.head_dim
        h = _rmsnorm(x, p["norm1"]["scale"], a.rms_eps)
        q = _linear(h, p["attn"]["wq"], quant).reshape(B, T, H, hd)
        k = _linear(h, p["attn"]["wk"], quant).reshape(B, T, K, hd)
        v = _linear(h, p["attn"]["wv"], quant).reshape(B, T, K, hd)
        q, k = _rope(q, a.rope_theta), _rope(k, a.rope_theta)
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = x + _linear(o.reshape(B, T, H * hd), p["attn"]["wo"], quant)
        h = _rmsnorm(x, p["norm2"]["scale"], a.rms_eps)
        g = jax.nn.silu(_linear(h, p["mlp"]["w_gate"], quant))
        u = _linear(h, p["mlp"]["w_up"], quant)
        return x + _linear(g * u, p["mlp"]["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("a", "quant"))
def _head(x, norm_scale, w, a: Arch, quant):
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(x, norm_scale, a.rms_eps)
        return _linear(x, w.T, quant)[..., :a.vocab]


def logits(params, a: Arch, tokens: np.ndarray, quant=None) -> jax.Array:
    """(B, T) token ids -> (B, T, vocab) float32 logits, causal."""
    x = jnp.take(params["embed"]["w"], jnp.asarray(tokens), axis=0)
    x = x.astype(F32)
    groups = params["groups"]["c0"]
    for i in range(a.n_layers):
        p = jax.tree.map(lambda w: w[i], groups)
        x = _layer(x, p, a, quant)
    return _head(x, params["final_norm"]["scale"], params["lm_head"]["w"],
                 a, quant)
