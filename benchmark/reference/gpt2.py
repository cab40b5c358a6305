"""Plain reference for GPT-2 (openai-community/gpt2-medium,
``modeling_gpt2.py``): forward pass and next-token loss.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no kernels, no remat, no scan, no fused loss, nothing from
``ray_tpu``'s model code.  Learned position embeddings, two layernorms per
block in sequence (pre-LN), biases everywhere, ``gelu_new``.

Departures, noted: the parameter tree is the program's (layers stacked on
a leading axis, fused ``attn_qkv``), because the reference runs on the
SAME weights; and the output head is the program's untied ``lm_head``
(the configuration lists it under ``assumed``: the published model ties
it to the token embedding).
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


def _block(x, w, n_heads):
    b, s, d = x.shape
    hd = d // n_heads
    a = _layernorm(x, w["ln1"]["scale"], w["ln1"]["bias"])
    qkv = a @ w["attn_qkv"]["kernel"] + w["attn_qkv"]["bias"]
    q, k, v = (t.reshape(b, s, n_heads, hd) for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + att.reshape(b, s, d) @ w["attn_out"]["kernel"] + w["attn_out"]["bias"]
    m = _layernorm(x, w["ln2"]["scale"], w["ln2"]["bias"])
    mid = _gelu_new(m @ w["mlp_in"]["kernel"] + w["mlp_in"]["bias"])
    return x + mid @ w["mlp_out"]["kernel"] + w["mlp_out"]["bias"]


def loss(params: dict, tokens, n_heads: int):
    """Mean next-token cross-entropy of ``tokens`` (batch, seq + 1)."""
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    tokens = jnp.asarray(tokens, jnp.int32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    block = jax.jit(_block, static_argnums=(2,))
    with jax.default_matmul_precision("highest"):
        s = inputs.shape[1]
        x = params["embed"]["tokens"][inputs] + params["embed"]["pos"][:s]
        n_layers = params["blocks"]["attn_qkv"]["kernel"].shape[0]
        for i in range(n_layers):
            w = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            x = block(x, w, n_heads)
        x = _layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
        logits = x @ params["lm_head"]["kernel"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return -picked.mean()
