"""Plain reference for Phi-4-mini-flash-reasoning (SambaY, arXiv:2507.06607):
the equations over a WHOLE sequence.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no cache, no ring, no block table, no kernel, no chunk,
nothing from ``ray_tpu``.  ``L`` layers, no positional encoding:

* block ``l``: ``h = x + Mixer_l(LN(x))``, ``y = h + W_down(silu(g) * u)``,
  ``[g, u] = LN'(h) W_gate_up``; LayerNorm with scale and bias; final
  LayerNorm; logits through the tied embedding;
* ``l < L/2`` even, and ``l = L/2``: state-space (Mamba-1): ``[u, z] = x
  W_in``; ``u = silu(conv(u) + b)``, causal, depthwise, width 4; ``[r, B,
  C] = u W_x``; ``delta = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``s_t = exp(delta_t A) * s_{t-1} + (delta_t u_t) B_t``; ``m_t = s_t C_t
  + D_skip * u_t``; out ``(m * silu(z)) W_out``.  The scan is a plain
  loop over the sequence's tokens.  Layer ``L/2``'s ``m`` is the MEMORY;
* ``l < L/2`` odd: differential attention, causal, over the last ``W``
  tokens (``t - s < W``); ``l = L/2 + 1``: the same, causal alone; its
  keys and values are the SHARED K/V;
* ``l > L/2 + 1`` even: memory gate, ``(m * silu(x W_1)) W_2``, ``m`` the
  memory at the same token; odd: differential attention of the layer's
  own queries over the shared K/V, causal;
* differential attention: query heads ``2i, 2i+1`` are pair ``i``, which
  reads key heads ``2g, 2g+1`` and the value ``[v_2g, v_2g+1]`` of pair ``g
  = i // (H/K)``: ``A_j = softmax(q_j k_j^T / sqrt(e) + mask) v``,
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_0(l)``, ``lambda_0(l)
  = 0.8 - 0.6 exp(-0.3 l)``, ``o = RMSNorm(A_1 - lambda A_2) (1 -
  lambda_0)``, dense masked softmaxes.

Departures, noted: the parameter tree is the program's, because the
reference must run on the SAME weights: layers of a kind stacked along a
leading axis in two segments (``seg1``: state-space and window by pairs;
``seg2``: gate and cross by pairs) around ``memory`` and ``full``, kernels
input-major, ``A_log`` stored ``(N, D)``.  Weights are upcast to float32
layer by layer, so a 16 GB chip can hold them in the dtype they are served
in.  What the published config does not give (the state-space sizes, the
pairing of heads, lambda, the layer kinds by index) is the configuration
file's ``assumed``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _layernorm(x, ln, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * ln["scale"] + ln["bias"]


def _mlp(h, w, eps):
    gu = _layernorm(h, w["ln2"], eps) @ w["mlp_gate_up"]["kernel"]
    half = gu.shape[-1] // 2
    return h + (jax.nn.silu(gu[:, :half]) * gu[:, half:]) @ w["mlp_down"]["kernel"]


@functools.partial(jax.jit, static_argnums=(2,))
def _ssm(x, w, eps):
    """State-space block over the whole sequence. Returns (y, memory m)."""
    w = _f32(w)
    D = w["D_skip"].shape[0]
    n_state, taps = w["A_log"].shape[0], w["conv"]["kernel"].shape[0]
    uz = _layernorm(x, w["ln1"], eps) @ w["in"]["kernel"]
    u, z = uz[:, :D], uz[:, D:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, D)), u])
    u = jax.nn.silu(
        sum(padded[i:i + x.shape[0]] * w["conv"]["kernel"][i] for i in range(taps))
        + w["conv"]["bias"])
    rbc = u @ w["x"]["kernel"]
    rank = rbc.shape[-1] - 2 * n_state
    r, b, c = rbc[:, :rank], rbc[:, rank:rank + n_state], rbc[:, rank + n_state:]
    delta = jax.nn.softplus(r @ w["dt"]["kernel"] + w["dt"]["bias"])
    a = -jnp.exp(w["A_log"])                                   # (N, D)

    def step(s, t):
        s = jnp.exp(delta[t] * a) * s + (delta[t] * u[t]) * b[t][:, None]
        return s, (s * c[t][:, None]).sum(0)

    _, m = jax.lax.scan(step, jnp.zeros((n_state, D)), jnp.arange(x.shape[0]))
    m = m + w["D_skip"] * u
    h = x + (m * jax.nn.silu(z)) @ w["out"]["kernel"]
    return _mlp(h, w, eps), m


def _diff_attention(q, k, v, mask, w, layer, n_heads, n_kv_heads, sub_eps):
    """q: (s, H e); k, v: (s, K e) of the tokens attended; mask (s, s).
    Every query pair at once: axes (pair, half, query, key)."""
    s = q.shape[0]
    e = q.shape[-1] // n_heads
    group = n_heads // n_kv_heads
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * layer)
    lam = (jnp.exp((w["lam"]["q1"] * w["lam"]["k1"]).sum())
           - jnp.exp((w["lam"]["q2"] * w["lam"]["k2"]).sum()) + lam0)
    q = q.reshape(s, n_heads // 2, 2, e)
    # query pair i reads key-value pair i // group
    k = jnp.repeat(k.reshape(s, n_kv_heads // 2, 2, e), group, axis=1)
    value = jnp.repeat(v.reshape(s, n_kv_heads // 2, 2 * e), group, axis=1)
    scores = jnp.einsum("sije,tije->ijst", q, k) / np.sqrt(e)
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    halves = jnp.einsum("ijst,tid->jsid", probs, value)        # (half, s, pair, 2e)
    o = halves[0] - lam * halves[1]
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + sub_eps) * w["subln"]["scale"]
    return (o * (1.0 - lam0)).reshape(s, -1) @ w["o"]["kernel"] + w["o"]["bias"]


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _attention(x, w, layer, window, n_heads, n_kv_heads, eps, sub_eps):
    """Window (``window`` > 0) or full attention block ``layer`` (a float:
    one program for every index). Returns (y, k, v)."""
    w = _f32(w)
    s = x.shape[0]
    e = x.shape[-1] // n_heads
    qkv = _layernorm(x, w["ln1"], eps) @ w["qkv"]["kernel"] + w["qkv"]["bias"]
    nq, nkv = n_heads * e, n_kv_heads * e
    q, k, v = qkv[:, :nq], qkv[:, nq:nq + nkv], qkv[:, nq + nkv:]
    t = jnp.arange(s)
    mask = t[:, None] >= t[None, :]
    if window:
        mask = mask & (t[:, None] - t[None, :] < window)
    h = x + _diff_attention(q, k, v, mask, w, layer, n_heads, n_kv_heads, sub_eps)
    return _mlp(h, w, eps), k, v


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _gate_and_cross(x, w, memory, k, v, layer, n_heads, n_kv_heads, eps, sub_eps):
    """Memory-gate block ``layer`` then cross-attention block ``layer + 1``."""
    w = _f32(w)
    gmu, cross = w["gmu"], w["cross"]
    gate = jax.nn.silu(_layernorm(x, gmu["ln1"], eps) @ gmu["in"]["kernel"])
    x = _mlp(x + (memory * gate) @ gmu["out"]["kernel"], gmu, eps)
    q = _layernorm(x, cross["ln1"], eps) @ cross["q"]["kernel"] + cross["q"]["bias"]
    t = jnp.arange(x.shape[0])
    h = x + _diff_attention(q, k, v, t[:, None] >= t[None, :], cross, layer + 1,
                            n_heads, n_kv_heads, sub_eps)
    return _mlp(h, cross, eps)


def logits_at(params: dict, tokens, rows, n_heads: int, n_kv_heads: int,
              window: int, eps: float, sub_eps: float):
    """float32 logits (len(rows), vocab) of ONE sequence ``tokens`` at the
    positions ``rows``, from a full forward pass over the whole sequence."""
    tokens = jnp.asarray(tokens, jnp.int32)
    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    n1 = params["seg1"]["ssm"]["D_skip"].shape[0]
    n2 = params["seg2"]["gmu"]["in"]["kernel"].shape[0]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        for i in range(n1):
            x, _ = _ssm(x, at(params["seg1"]["ssm"], i), eps)
            x, _, _ = _attention(x, at(params["seg1"]["window"], i), 2.0 * i + 1, window,
                                 n_heads, n_kv_heads, eps, sub_eps)
        x, memory = _ssm(x, params["memory"], eps)
        x, k, v = _attention(x, params["full"], 2.0 * n1 + 1, 0, n_heads, n_kv_heads,
                             eps, sub_eps)
        for i in range(n2):
            x = _gate_and_cross(x, at(params["seg2"], i), memory, k, v, 2.0 * n1 + 2 + 2 * i,
                                n_heads, n_kv_heads, eps, sub_eps)
        h = _layernorm(x[jnp.asarray(rows)], _f32(params["ln_f"]), eps)
        return h @ params["embed"]["tokens"].astype(jnp.float32).T
