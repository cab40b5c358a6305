"""Plain reference for Kimi-K2.5's language model (``model_type`` ``kimi_k2``,
DeepSeek-V3's block) as ONE chip of an expert-parallel deployment holds it:
the equations over a WHOLE sequence.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no cache, no block table, no kernel, no chunk, no absorbed
form, nothing from ``ray_tpu``.  ``h`` the residual stream, RMSNorm at
``eps``:

* attention: ``x = RMSNorm(h)``; ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb``
  as heads of ``[q_nope, q_rope]``; ``[c_kv, k_r] = x W_kva``, ``c =
  RMSNorm(c_kv)``; ``q_rope`` and ``k_r`` rotated at the token's position
  (half-split lanes, YaRN's ``inv_freq``); EXPANDED: ``k_nope = c W_kvb^K``,
  ``v = c W_kvb^V`` for every head; scores ``(q_nope . k_nope + q_rope .
  k_r) * scale``, causal softmax, times ``v``, then ``W_o``.  Queries go in
  blocks of 512 so a 6k-token probe's scores fit;
* the leading dense layers: ``W_down(silu(x W_gate) * (x W_up))``;
* the expert layers: ``p = sigmoid(x W_r)`` over ALL experts, the top ``k``
  of ``p + b`` chosen, weights ``p[chosen] / (sum + 1e-20) * scaling``; ``y =
  sum over chosen AND held e of w_e Expert_e(x) + Shared(x)``, a plain loop
  over the held experts ``offset .. offset + held``, each on every token
  with the weight 0 where it was not chosen.  The absent experts' part is
  left out: the reference is given the same share as the program;
* final RMSNorm, untied head (the slice of the vocabulary held).

**Where the equations do not determine the answer.**  Choosing the top ``k``
of 384 scores is discontinuous: where a held expert's score lies within
``ROUTING_MARGIN`` of the boundary (the 9th score for a chosen expert, the
8th for one not chosen), a program whose products round to bfloat16 upstream
of the router chooses either way, and both are this configuration's answer;
a 0.35-weighted expert then enters or leaves the stream, which moves that
position's logits by up to 1.5.  The reference knows where these positions
are from its OWN scores, and ``logits_at`` says nothing there: such a row
comes back all zero, so the harness's comparison (the reference's largest
logit less its logit of the served token) reads 0 on it and is decided by
the rows whose routing the equations do determine.  A position's margin is
the smallest over the expert layers and the held experts; ``forward``
returns it for every position beside the choices.

Departures, noted: the parameter tree is the program's, because the
reference must run on the SAME weights (the dense and the expert layers
stacked along a leading axis each, kernels input-major, ``W_kvb`` as its key
and its value columns ``(rank, heads, width)``); weights are upcast to
float32 layer by layer and expert by expert, so a 16 GB chip holds them in
the dtype they are served in.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
#: in units of the selection score ``p + b``; kimi-k2.5-ep32-l7-1chip's
#: ``correctness`` has the readings it stands between (a test holds the two equal)
ROUTING_MARGIN = 0.0075


def yarn_inv_freq(rope: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """``f_i = theta^(-2i / rope)``; ramp ``r_i = clip((i - low) / (high -
    low), 0, 1)`` between the correction dims of ``beta_fast`` (floor) and
    ``beta_slow`` (ceil); ``inv_freq_i = f_i (1 - r_i) + (f_i / factor) r_i``."""
    i = np.arange(rope // 2, dtype=np.float64)
    f = theta ** (-2 * i / rope)
    dim_of = lambda rot: rope * math.log(original / (rot * 2 * math.pi)) / (  # noqa: E731
        2 * math.log(theta))
    # clamped to the lanes there are, as the published code does
    low, high = max(math.floor(dim_of(beta_fast)), 0), min(math.ceil(dim_of(beta_slow)), rope - 1)
    r = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (f * (1 - r) + f / factor * r).astype(np.float32)


def softmax_scale(head_dim: int, factor: float, mscale_all_dim: float) -> float:
    return head_dim ** -0.5 * (0.1 * mscale_all_dim * math.log(factor) + 1.0) ** 2


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate(x, positions, inv_freq):
    """x: (s, ..., rope); lanes [0 : rope/2] turn with [rope/2 : rope]."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (inv_freq.shape[0],)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _attention(h, w, n_heads, nope, eps, scale, inv_freq):
    w = {k: _f32(v) for k, v in w.items()
         if k in ("ln1", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b_k", "kv_b_v", "o")}
    s = h.shape[0]
    rank = w["kv_a_norm"]["scale"].shape[0]
    positions = jnp.arange(s)
    x = _rmsnorm(h, w["ln1"]["scale"], eps)
    c_q = _rmsnorm(x @ w["q_a"]["kernel"], w["q_a_norm"]["scale"], eps)
    q = (c_q @ w["q_b"]["kernel"]).reshape(s, n_heads, -1)
    q_nope, q_rope = q[..., :nope], _rotate(q[..., nope:], positions, inv_freq)
    kv = x @ w["kv_a"]["kernel"]
    c = _rmsnorm(kv[:, :rank], w["kv_a_norm"]["scale"], eps)
    k_r = _rotate(kv[:, rank:], positions, inv_freq)
    k_nope = jnp.einsum("tr,rhd->thd", c, w["kv_b_k"]["kernel"])
    v = jnp.einsum("tr,rhd->thd", c, w["kv_b_v"]["kernel"])
    outs = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        scores = (jnp.einsum("qhd,thd->hqt", q_nope[lo:hi], k_nope)
                  + jnp.einsum("qhd,td->hqt", q_rope[lo:hi], k_r)) * scale
        causal = positions[lo:hi, None] >= positions[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqt,thd->qhd", probs, v))
    return h + jnp.concatenate(outs).reshape(s, -1) @ w["o"]["kernel"]


@functools.partial(jax.jit, static_argnums=(2,))
def _dense_mlp(h, w, eps):
    x = _rmsnorm(h, w["ln2"]["scale"].astype(jnp.float32), eps)
    return h + _swiglu(x, *(w["mlp"][k].astype(jnp.float32) for k in ("gate", "up", "down")))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _expert_mlp(h, w, eps, top_k, scaling, offset):
    """Returns (h', held (s, held) bool: which held experts each token
    chose, margin (s,): the least distance of a held expert's score from the
    boundary it would have to cross to be chosen otherwise)."""
    x = _rmsnorm(h, w["ln2"]["scale"].astype(jnp.float32), eps)
    p = jax.nn.sigmoid(x @ w["router"]["kernel"].astype(jnp.float32))
    scores = p + w["router"]["bias"].astype(jnp.float32)
    top, chosen = jax.lax.top_k(scores, top_k + 1)
    last_in, first_out, chosen = top[:, top_k - 1:top_k], top[:, top_k:], chosen[:, :top_k]
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scaling
    held = w["experts"]["gate"].shape[0]
    y = _swiglu(x, *(w["shared"][k].astype(jnp.float32) for k in ("gate", "up", "down")))
    masks = []
    for e in range(held):
        mine = chosen == offset + e                                     # (s, top_k)
        w_e = (weights * mine).sum(-1, keepdims=True)
        y = y + w_e * _swiglu(
            x, *(w["experts"][k][e].astype(jnp.float32) for k in ("gate", "up", "down")))
        masks.append(mine.any(-1))
    masks = jnp.stack(masks, axis=-1)
    mine = scores[:, offset:offset + held]
    margin = jnp.where(masks, mine - first_out, last_in - mine).min(axis=-1)
    return h + y, masks, margin


def forward(params: dict, tokens, *, n_heads: int, nope: int, rope: int, eps: float,
            scale: float, inv_freq, top_k: int, scaling: float, offset: int):
    """(the stream after the last layer (s, d), [per expert layer: (s, held)
    bool, which held experts each token chose], [per expert layer: (s,) each
    token's routing margin]) of ONE sequence."""
    tokens = jnp.asarray(tokens, jnp.int32)
    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    inv_freq = jnp.asarray(inv_freq, jnp.float32)
    assert inv_freq.shape[0] == rope // 2
    held, margins = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(jnp.float32)
        dense, moe = params["dense"], params["moe"]
        for i in range(dense["ln1"]["scale"].shape[0]):
            w = at(dense, i)
            x = _dense_mlp(_attention(x, w, n_heads, nope, eps, scale, inv_freq), w, eps)
        for i in range(moe["ln1"]["scale"].shape[0]):
            w = at(moe, i)
            x, mask, margin = _expert_mlp(
                _attention(x, w, n_heads, nope, eps, scale, inv_freq), w, eps, top_k,
                scaling, offset)
            held.append(mask)
            margins.append(margin)
    return x, held, margins


def logits_at(params: dict, tokens, rows, margin: float = 0.0, **sizes):
    """float32 logits (len(rows), vocab held) of ONE sequence ``tokens`` at
    the positions ``rows``, from a full forward pass over the whole
    sequence; a row whose own routing lies within ``margin`` of a boundary
    is all zero (the module's note; the family passes ``ROUTING_MARGIN`` for
    a program in bfloat16 and 0 for one in float32, whose choice IS
    determined)."""
    x, _, margins = forward(params, tokens, **sizes)
    rows = jnp.asarray(rows)
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x[rows], params["ln_f"]["scale"].astype(jnp.float32), sizes["eps"])
        logits = h @ params["lm_head"]["kernel"].astype(jnp.float32)
    determined = jnp.stack(margins).min(axis=0)[rows] >= margin
    return jnp.where(determined[:, None], logits, 0.0)
