"""Plain reference for GPT-J (EleutherAI/gpt-j-6b, ``modeling_gptj.py``).

Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no cache, no kernels, no batching, no scan, nothing from
``ray_tpu``'s model code.  It follows the published model: one layernorm
per block feeding attention and MLP in parallel, rotary embedding on the
first ``rotary_dim`` dimensions of every head in the interleaved
(rotate-every-two) form, no biases on q/k/v/out, ``gelu_new``, an untied
output head with a bias.

Departure, noted: the parameter tree is the program's (``blocks`` stacked
along a leading layer axis, kernels stored input-major), because the
reference must run on the SAME weights; they are upcast to float32 layer
by layer, so a 16 GB chip can hold them in the dtype they are served in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _layernorm(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x**3)))


def _rotate_every_two(x):
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack((-x2, x1), axis=-1).reshape(x.shape)


def _rotary(x, positions, rotary_dim):
    """x: (s, heads, head_dim)."""
    inv_freq = 1.0 / (10000.0 ** (np.arange(0, rotary_dim, 2) / rotary_dim))
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)[:, None, :]
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)[:, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    rot = rot * cos + _rotate_every_two(rot) * sin
    return jnp.concatenate([rot, rest], axis=-1)


def _block(x, w, positions, n_heads, rotary_dim):
    s, d = x.shape
    hd = d // n_heads
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = _layernorm(x, f32(w["ln1"]["scale"]), f32(w["ln1"]["bias"]))
    q = (h @ f32(w["q"]["kernel"])).reshape(s, n_heads, hd)
    k = (h @ f32(w["k"]["kernel"])).reshape(s, n_heads, hd)
    v = (h @ f32(w["v"]["kernel"])).reshape(s, n_heads, hd)
    q, k = _rotary(q, positions, rotary_dim), _rotary(k, positions, rotary_dim)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    att = att.reshape(s, d) @ f32(w["attn_out"]["kernel"])
    mid = _gelu_new(h @ f32(w["mlp_in"]["kernel"]) + f32(w["mlp_in"]["bias"]))
    mlp = mid @ f32(w["mlp_out"]["kernel"]) + f32(w["mlp_out"]["bias"])
    return x + att + mlp


def logits_at(params: dict, tokens, rows, n_heads: int, rotary_dim: int):
    """float32 logits (len(rows), vocab) of ONE sequence ``tokens`` at the
    positions ``rows``, from a full forward pass over the whole sequence."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0])
    block = jax.jit(_block, static_argnums=(3, 4))
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        n_layers = params["blocks"]["q"]["kernel"].shape[0]
        for i in range(n_layers):
            w = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            x = block(x, w, positions, n_heads, rotary_dim)
        h = _layernorm(
            x[jnp.asarray(rows)],
            params["ln_f"]["scale"].astype(jnp.float32),
            params["ln_f"]["bias"].astype(jnp.float32),
        )
        return (
            h @ params["lm_head"]["kernel"].astype(jnp.float32)
            + params["lm_head"]["bias"].astype(jnp.float32)
        )
