"""Plain reference for Falcon-H1 (tiiuae/Falcon-H1-34B-Instruct,
``model_type`` ``falcon_h1``): the equations over a WHOLE sequence.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no cache, no state pool, no block table, no chunk, no kernel,
nothing from ``ray_tpu``.  ``h`` the residual stream, ``RMSNorm`` with a
learned scale at ``rms_norm_eps``:

* ``h = E[token] * embedding_multiplier``;
* block: ``u = RMSNorm_in(h)``; ``h += ssm_out_multiplier * SSM(u) +
  attention_out_multiplier * Attn(u * attention_in_multiplier)``; then ``h +=
  MLP(RMSNorm_ff(h))``;
* SSM (Mamba-2): ``p = ((u * ssm_in_multiplier) W_in) . m``, ``W_in``'s
  columns ``[z | x | B | C | dt]`` and ``m`` the five ``ssm_multipliers`` laid
  over them; ``[x | B | C]`` through a causal depthwise convolution of width
  ``d_conv`` with bias, then SiLU; ``dt = softplus(dt + dt_bias)``; ``A =
  -exp(A_log)``; heads in ``n_groups`` consecutive runs, each run reading its
  group's ``B`` and ``C``; ``S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h]
  x_t[h] (x) B_t[g(h)]``, ``y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]``: a plain
  loop over the sequence's tokens; ``y = RMSNorm_grouped(y . silu(z))``,
  normalised within each group, learned scale; ``SSM = y W_out``;
* attention: ``q = a W_q``, ``k = (a W_k) * key_multiplier``, ``v = a W_v``;
  rotary over the whole head (half-split form: lane ``i`` with lane ``i + e /
  2`` at ``theta ** (-2i / e)``); a dense causal softmax of ``q . k /
  sqrt(e)``, query head ``i`` on key-value head ``i // (H / K)``; ``concat
  W_o``;
* MLP: ``W_down(silu((v W_gate) * mlp_multipliers[0]) . (v W_up)) *
  mlp_multipliers[1]``;
* ``logits = (RMSNorm_f(h) W_head) * lm_head_multiplier``.

Departures, noted: the parameter tree is the program's (``blocks`` stacked
along a leading layer axis, kernels input-major), because the reference must
run on the SAME weights; they are upcast to float32 layer by layer, so a 16
GB chip can hold them in the dtype they are served in.  The softmax runs one
key-value head at a time (its ``H / K`` query heads: 1.6 GB of scores at
9,000 tokens where all 20 heads at once would be 6.5), and the MLP in row
blocks: the same numbers.  What the published config does not give (the
rotary's form, the state's dtype, the initializer) is the configuration
file's ``assumed``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: rows of the sequence the MLP takes at a time (its 21,504-wide middle)
_MLP_ROWS = 2048


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotary(x, theta):
    """x: (s, heads, e) at positions 0..s-1."""
    s, _, e = x.shape
    inv_freq = theta ** (-np.arange(0, e, 2, dtype=np.float64) / e)
    ang = jnp.asarray(np.arange(s)[:, None] * inv_freq[None, :], jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _ssm(u, w, c):
    s = u.shape[0]
    heads, groups, n_state, taps = c["ssm_heads"], c["n_groups"], c["d_state"], c["d_conv"]
    d_ssm = w["ssm_out"]["kernel"].shape[0]
    gn = groups * n_state
    m = np.concatenate([np.full(width, mult, np.float32) for width, mult in zip(
        (d_ssm, d_ssm, gn, gn, heads), c["ssm_multipliers"])])
    p = ((u * c["ssm_in_multiplier"]) @ w["ssm_in"]["kernel"]) * m
    z, xbc, dt = p[:, :d_ssm], p[:, d_ssm:2 * d_ssm + 2 * gn], p[:, 2 * d_ssm + 2 * gn:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(
        sum(padded[i:i + s] * w["conv"]["kernel"][i] for i in range(taps)) + w["conv"]["bias"])
    x = xbc[:, :d_ssm].reshape(s, heads, -1)
    # head h reads group h // (heads / groups)
    b = jnp.repeat(xbc[:, d_ssm:d_ssm + gn].reshape(s, groups, n_state), heads // groups, axis=1)
    cc = jnp.repeat(xbc[:, d_ssm + gn:].reshape(s, groups, n_state), heads // groups, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["A_log"])

    def step(state, t):
        state = jnp.exp(dt[t] * a)[:, None, None] * state \
            + (dt[t][:, None] * x[t])[:, :, None] * b[t][:, None, :]
        return state, (state * cc[t][:, None, :]).sum(-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, x.shape[-1], n_state)), jnp.arange(s))
    y = (y + w["D"][:, None] * x).reshape(s, d_ssm) * jax.nn.silu(z)
    y = y.reshape(s, groups, -1)
    y = y / jnp.sqrt((y * y).mean(-1, keepdims=True) + c["rms_norm_eps"])
    return (y.reshape(s, d_ssm) * w["ssm_norm"]["scale"]) @ w["ssm_out"]["kernel"]


def _attention(a, w, c):
    s = a.shape[0]
    hq, hkv, e = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    q = _rotary((a @ w["q"]["kernel"]).reshape(s, hq, e), c["rope_theta"])
    k = _rotary(((a @ w["k"]["kernel"]) * c["key_multiplier"]).reshape(s, hkv, e),
                c["rope_theta"])
    v = (a @ w["v"]["kernel"]).reshape(s, hkv, e)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_kv_head(qkv):
        qg, kh, vh = qkv                                   # (s, H/K, e), (s, e), (s, e)
        scores = jnp.einsum("sge,te->gst", qg, kh) / np.sqrt(e)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gst,te->sge", probs, vh)

    out = jax.lax.map(one_kv_head, (
        q.reshape(s, hkv, hq // hkv, e).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))       # (K, s, H/K, e)
    return out.transpose(1, 0, 2, 3).reshape(s, hq * e) @ w["o"]["kernel"]


def _mlp(h, w, c):
    rows = min(_MLP_ROWS, h.shape[0])
    pad = -h.shape[0] % rows

    def block(x):
        v = _rmsnorm(x, w["ln_ff"]["scale"], c["rms_norm_eps"])
        mid = jax.nn.silu((v @ w["gate"]["kernel"]) * c["mlp_multipliers"][0]) * (
            v @ w["up"]["kernel"])
        return (mid @ w["down"]["kernel"]) * c["mlp_multipliers"][1]

    out = jax.lax.map(block, jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, rows, h.shape[1]))
    return h + out.reshape(-1, h.shape[1])[:h.shape[0]]


@functools.partial(jax.jit, static_argnums=(2,))
def _block(h, w, consts):
    c = dict(consts)
    w = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), w)
    u = _rmsnorm(h, w["ln_in"]["scale"], c["rms_norm_eps"])
    h = h + c["ssm_out_multiplier"] * _ssm(u, w, c) \
        + c["attention_out_multiplier"] * _attention(u * c["attention_in_multiplier"], w, c)
    return _mlp(h, w, c)


def logits_at(params: dict, tokens, rows, consts: dict):
    """float32 logits (len(rows), vocab) of ONE sequence ``tokens`` at the
    positions ``rows``, from a full forward pass over the whole sequence.
    ``consts``: the configuration's numbers by the program's field names
    (heads, sizes of the mixer, ``rms_norm_eps``, ``rope_theta``, every
    multiplier)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    frozen = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                          for k, v in consts.items()))
    n_layers = params["blocks"]["ln_in"]["scale"].shape[0]
    with jax.default_matmul_precision("highest"):
        h = params["embed"]["tokens"][tokens].astype(jnp.float32) * consts[
            "embedding_multiplier"]
        for i in range(n_layers):
            h = _block(h, jax.tree_util.tree_map(lambda a: a[i], params["blocks"]), frozen)
        y = _rmsnorm(h[jnp.asarray(rows)], params["ln_f"]["scale"].astype(jnp.float32),
                     consts["rms_norm_eps"])
        return (y @ params["lm_head"]["kernel"].astype(jnp.float32)) * consts[
            "lm_head_multiplier"]
