"""Plain reference for Brumby (manifestai/Brumby-14B-Base): power retention
in its ATTENTION form.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no recurrent state, no feature map, no cache, no kernels, no
batching, no scan, nothing from ``ray_tpu``.  Pre-norm block: ``x +=
Ret(RMSNorm(x))`` then ``x += W_down(silu(W_gate h) * W_up h)`` with ``h =
RMSNorm(x)``; final RMSNorm, untied head, no biases.  ``Ret``, per key-value
head with its group of query heads: q and k through a per-head RMSNorm and
rotary over the whole head (half-split form); ``log g_t = log_sigmoid(x_t
W_g + gate_shift)``, ``a_t`` its running sum; ``w_ts = exp(a_t - a_s) (q_t .
k_s / sqrt(d))^2`` for ``s <= t``; ``y_t = sum_s w_ts v_s / (sum_s w_ts +
eps)``.  Quadratic in the sequence's length, which is what makes it plain:
the program's recurrent state must give the same numbers.

Departures, noted: the parameter tree is the program's (``blocks`` stacked
along a leading layer axis, kernels stored input-major), because the
reference must run on the SAME weights; they are upcast to float32 layer by
layer, so a 16 GB chip can hold them in the dtype they are served in.  The
sizes the published config does not give (the power 2, one gate a key-value
head, ``gate_shift``, ``eps``) are the configuration file's ``assumed``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotary(x, positions, theta):
    """x: (s, heads, head_dim); rotate_half form over the whole head."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (np.arange(half) / half))
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def _block(x, w, positions, n_heads, n_kv_heads, rms_eps, theta, gate_shift, eps):
    s, d = x.shape
    hd = w["q"]["kernel"].shape[-1] // n_heads
    group = n_heads // n_kv_heads
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = _rmsnorm(x, f32(w["ln1"]["scale"]), rms_eps)
    q = (h @ f32(w["q"]["kernel"])).reshape(s, n_heads, hd)
    k = (h @ f32(w["k"]["kernel"])).reshape(s, n_kv_heads, hd)
    v = (h @ f32(w["v"]["kernel"])).reshape(s, n_kv_heads, hd)
    q = _rotary(_rmsnorm(q, f32(w["q_norm"]["scale"]), rms_eps), positions, theta)
    k = _rotary(_rmsnorm(k, f32(w["k_norm"]["scale"]), rms_eps), positions, theta)
    log_g = jax.nn.log_sigmoid(h @ f32(w["gate"]["kernel"]) + gate_shift)  # (s, kv)
    a = jnp.cumsum(log_g, axis=0)
    causal = positions[:, None] >= positions[None, :]
    outs = []
    for kv in range(n_kv_heads):
        decay = jnp.exp(a[:, None, kv] - a[None, :, kv])
        for j in range(kv * group, (kv + 1) * group):
            scores = (q[:, j] @ k[:, kv].T) / np.sqrt(hd)
            wts = jnp.where(causal, decay * scores**2, 0.0)
            outs.append((wts @ v[:, kv]) / (wts.sum(-1, keepdims=True) + eps))
    x = x + jnp.concatenate(outs, axis=-1) @ f32(w["attn_out"]["kernel"])
    h = _rmsnorm(x, f32(w["ln2"]["scale"]), rms_eps)
    mid = jax.nn.silu(h @ f32(w["mlp_gate"]["kernel"])) * (h @ f32(w["mlp_up"]["kernel"]))
    return x + mid @ f32(w["mlp_down"]["kernel"])


def logits_at(params: dict, tokens, rows, n_heads: int, n_kv_heads: int,
              rms_eps: float, rope_theta: float, gate_shift: float, eps: float):
    """float32 logits (len(rows), vocab) of ONE sequence ``tokens`` at the
    positions ``rows``, from a full forward pass over the whole sequence."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0])
    block = jax.jit(_block, static_argnums=(3, 4, 5, 6, 7, 8))
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        n_layers = params["blocks"]["q"]["kernel"].shape[0]
        for i in range(n_layers):
            w = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            x = block(x, w, positions, n_heads, n_kv_heads, rms_eps, rope_theta,
                      gate_shift, eps)
        h = _rmsnorm(x[jnp.asarray(rows)], params["ln_f"]["scale"].astype(jnp.float32),
                     rms_eps)
        return h @ params["lm_head"]["kernel"].astype(jnp.float32)
