"""Plain reference for Granite-4.0-H (ibm-granite/granite-4.0-h-small,
``model_type`` ``granitemoehybrid``) as ONE chip of an expert-parallel
deployment holds it: the equations over a WHOLE sequence.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no cache, no state pool, no block table, no chunk, no kernel,
no tiles, nothing from ``ray_tpu``.  ``h`` the residual stream, ``RMSNorm``
with a learned scale at ``rms_norm_eps``:

* ``h = E[token] * embedding_multiplier``;
* a layer: ``h += residual_multiplier * Mixer(RMSNorm_1(h))``; then ``y =
  RMSNorm_2(h)``, ``h += residual_multiplier * (Routed(y) + Shared(y))``;
* Mixer of a ``mamba`` layer (Mamba-2): ``[z | xBC | dt] = u W_in``; ``xBC``
  through a causal depthwise convolution of width ``d_conv`` with bias, then
  SiLU, split ``[x | B | C]``; ``dt = softplus(dt + dt_bias)``; ``A =
  -exp(A_log)``; heads in ``n_groups`` consecutive runs, each reading its
  group's ``B`` and ``C``; ``S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h]
  x_t[h] (x) B_t[g(h)]``, ``y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]``: a plain
  loop over the sequence's tokens; ``RMSNorm(y . silu(z))`` within each group
  (ONE at the published sizes), learned scale; then ``W_out``;
* Mixer of an ``attention`` layer: ``q``, ``k``, ``v`` with NO positional
  encoding; a dense causal softmax of ``q . k * attention_multiplier``, query
  head ``i`` on key-value head ``i // (H / K)``; then ``W_o``;
* Routed: ``z = y W_r`` over ALL experts, the ``top_k`` largest logits chosen,
  ``w = softmax(z[chosen])``; a plain loop over the held experts ``offset ..
  offset + held``, each on every token with the weight 0 where it was not
  chosen: ``(silu(y W_gate,e) . (y W_up,e)) W_down,e``.  The absent experts'
  part is left out: the reference is given the same share as the program.
  Shared: the same gated form, added whole;
* ``logits = RMSNorm_f(h) E^T / logits_scaling`` (the head is tied).

**Routing is discontinuous.**  Choosing the 10 largest of 72 logits flips
where the 10th and the 11th lie closer than bfloat16 products upstream of the
router move them, and both choices are this configuration's answer.
``forward`` returns, for every position and expert layer, how near that is:
``gap`` (the 10th logit less the 11th, where one of the two experts is HELD
here; infinite where neither is: such a flip is the other chip's) and
``weight`` (the 10th's softmax weight: what a flip takes out of the stream).
``logits_at`` says nothing (a row all zero, on which the harness's
comparison reads 0) on a row with ``gap < margin`` and ``weight >
min_weight`` in some layer; at ``margin`` 0, the configuration's
(``correctness.routing_margin`` has the readings behind it), every row is
compared.

Departures, noted: the parameter tree is the program's, because the
reference must run on the SAME weights: ``runs`` (one stack of layers for
each run of one kind, kernels input-major), the published ``W_in,e``'s two
halves as ``gate`` and ``up``, ``experts`` EVERY layer's held experts flat,
layer ``l``'s from ``l * held``.  Weights are upcast to float32 layer by
layer and expert by expert, so a 16 GB chip holds them in the dtype they are
served in; the softmax runs one key-value head at a time and the recurrence
carries one sequence's state: the same numbers.  What the published config
does not give (dtypes, initializers) is the configuration file's ``assumed``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnums=(2,))
def _mamba(h, w, consts):
    c = dict(consts)
    w = _f32({k: w[k] for k in ("ln1", "ssm_in", "conv", "dt_bias", "A_log", "D", "ssm_norm",
                                "ssm_out")})
    s = h.shape[0]
    heads, groups, n_state, taps = c["ssm_heads"], c["n_groups"], c["d_state"], c["d_conv"]
    d_ssm, gn = w["ssm_out"]["kernel"].shape[0], groups * n_state
    p = _rmsnorm(h, w["ln1"]["scale"], c["rms_norm_eps"]) @ w["ssm_in"]["kernel"]
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
    mixed = (y.reshape(s, d_ssm) * w["ssm_norm"]["scale"]) @ w["ssm_out"]["kernel"]
    return h + c["residual_multiplier"] * mixed


@functools.partial(jax.jit, static_argnums=(2,))
def _attention(h, w, consts):
    c = dict(consts)
    w = _f32({k: w[k] for k in ("ln1", "q", "k", "v", "o")})
    s = h.shape[0]
    hq, hkv, e = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    u = _rmsnorm(h, w["ln1"]["scale"], c["rms_norm_eps"])
    q = (u @ w["q"]["kernel"]).reshape(s, hkv, hq // hkv, e)
    k = (u @ w["k"]["kernel"]).reshape(s, hkv, e)
    v = (u @ w["v"]["kernel"]).reshape(s, hkv, e)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_kv_head(qkv):
        qg, kh, vh = qkv                                   # (s, H/K, e), (s, e), (s, e)
        scores = jnp.einsum("sge,te->gst", qg, kh) * c["attention_multiplier"]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gst,te->sge", probs, vh)

    out = jax.lax.map(one_kv_head, (
        q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # (K, s, H/K, e)
    mixed = out.transpose(1, 0, 2, 3).reshape(s, hq * e) @ w["o"]["kernel"]
    return h + c["residual_multiplier"] * mixed


@functools.partial(jax.jit, static_argnums=(3,))
def _experts(h, w, experts, consts):
    """Returns (h', held (s, held) bool: which held experts each token chose,
    gap (s,), weight (s,): the module's note)."""
    c = dict(consts)
    top_k, offset = c["experts_per_tok"], c["expert_offset"]
    held = experts["gate"].shape[0]
    y = _rmsnorm(h, w["ln2"]["scale"].astype(jnp.float32), c["rms_norm_eps"])
    z = y @ w["router"]["kernel"].astype(jnp.float32)
    top, order = jax.lax.top_k(z, top_k + 1)
    chosen, weights = order[:, :top_k], jax.nn.softmax(top[:, :top_k], axis=-1)

    def one_expert(out, e):
        w_e = (weights * (chosen == offset + e)).sum(-1, keepdims=True)
        mine = _f32(jax.tree_util.tree_map(lambda a: a[e], experts))
        return out + w_e * _swiglu(y, mine["gate"], mine["up"], mine["down"]), None

    shared = _f32(w["shared"])
    out, _ = jax.lax.scan(
        one_expert, _swiglu(y, shared["gate"], shared["up"], shared["down"]), jnp.arange(held))
    is_held = lambda e: (e >= offset) & (e < offset + held)  # noqa: E731
    ours = is_held(order[:, top_k - 1]) | is_held(order[:, top_k])
    gap = jnp.where(ours, top[:, top_k - 1] - top[:, top_k], jnp.inf)
    masks = (chosen[:, :, None] == offset + jnp.arange(held)).any(axis=1)
    return h + c["residual_multiplier"] * out, masks, gap, weights[:, top_k - 1]


def _frozen(consts: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in consts.items()))


def forward(params: dict, tokens, consts: dict):
    """(the stream after the last layer (s, d), and a list per layer of:
    (s, held) bool, which held experts each token chose; (s,) each token's
    ``gap``; (s,) each token's ``weight``) of ONE sequence.  ``consts``: the
    configuration's numbers by the program's field names."""
    tokens = jnp.asarray(tokens, jnp.int32)
    frozen = _frozen(consts)
    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    held = params["experts"]["gate"].shape[0] // sum(
        jax.tree_util.tree_leaves(run)[0].shape[0] for run in params["runs"])
    masks, gaps, weights, layer = [], [], [], 0
    with jax.default_matmul_precision("highest"):
        h = params["embed"]["tokens"][tokens].astype(jnp.float32) * consts[
            "embedding_multiplier"]
        for run in params["runs"]:
            mixer = _mamba if "ssm_in" in run else _attention
            for i in range(run["ln1"]["scale"].shape[0]):
                w = at(run, i)
                mine = jax.tree_util.tree_map(
                    lambda a: a[layer * held:(layer + 1) * held], params["experts"])
                h, mask, gap, weight = _experts(mixer(h, w, frozen), w, mine, frozen)
                masks.append(mask)
                gaps.append(gap)
                weights.append(weight)
                layer += 1
    return h, masks, gaps, weights


def logits_at(params: dict, tokens, rows, consts: dict, margin: float = 0.0,
              min_weight: float = 0.0):
    """float32 logits (len(rows), vocab held) of ONE sequence ``tokens`` at
    the positions ``rows``, from a full forward pass over the whole sequence;
    a row with a boundary pair within ``margin`` that carries more than
    ``min_weight`` in some layer is all zero (the module's note)."""
    h, _, gaps, weights = forward(params, tokens, consts)
    rows = jnp.asarray(rows)
    with jax.default_matmul_precision("highest"):
        y = _rmsnorm(h[rows], params["ln_f"]["scale"].astype(jnp.float32),
                     consts["rms_norm_eps"])
        logits = y @ params["embed"]["tokens"].astype(jnp.float32).T / consts["logits_scaling"]
    near = (jnp.stack(gaps) < margin) & (jnp.stack(weights) > min_weight)
    return jnp.where(near.any(axis=0)[rows][:, None], 0.0, logits)
