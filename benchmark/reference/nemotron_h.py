"""Plain reference for Nemotron-H (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``model_type`` ``nemotron_h``) as ONE chip of an expert-parallel deployment
holds it: the equations over a WHOLE sequence, as Hugging Face's
``modeling_nemotron_h.py`` computes them.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no cache, no state pool, no block table, no chunk, no kernel,
nothing from ``ray_tpu``.  ``h`` the residual stream, ``RMSNorm`` with a
learned scale at ``norm_eps``:

* ``h = E[token]``; a layer is ONE part: ``h += Part(RMSNorm(h))``, the part
  its letter of ``hybrid_override_pattern`` names; ``logits = RMSNorm_f(h)
  W_head`` (the head is not tied);
* ``M`` (Mamba-2): ``[z | xBC | dt] = u W_in``; ``xBC`` through a causal
  depthwise convolution of ``d_conv`` taps with bias, then SiLU, split ``[x | B
  | C]``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; heads in
  ``n_groups`` consecutive runs, each reading its group's ``B`` and ``C``;
  ``S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)]``,
  ``y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]``: a plain loop over the
  sequence's tokens; ``RMSNorm(y . silu(z))`` within each group, learned
  scale; then ``W_out``;
* ``*``: ``q``, ``k``, ``v`` with NO positional encoding; a dense causal
  softmax of ``q . k / sqrt(e)``, query head ``i`` on key-value head ``i // (H
  / K)``; then ``W_o``;
* ``E``: ``s = sigmoid(y W_r)`` over ALL experts, the ``top_k`` largest of ``s
  + bias`` chosen, ``w = s[chosen] / (sum + eps) * scaling``; a plain loop over
  the held experts ``offset .. offset + held``, each on every token with the
  weight 0 where it was not chosen: ``relu(y W_up,e)**2 W_down,e`` (TWO
  matrices, no gate).  The absent experts' part is left out: the reference is
  given the same share as the program.  Shared: the same ungated form, added
  whole.

**Routing is discontinuous.**  ``forward`` returns, for every position and
expert layer, ``gap``: the 6th chosen score less the 7th, where one of the two
experts is HELD here (infinite where neither is: such a flip is the other
chips').  EVERY row is compared (the configuration's
``correctness.routing_flips`` has why); the controls' witness reads the gaps
and the masks.  ``taps`` (``{"rows": positions}``) gets under ``"layers"``, an
expert layer, the stream that ENTERED it at those positions, what its ROUTED
part added there (the shared expert's is left out) and the rows' gaps: what
the family's expert-layer probe holds the program's routed experts to, layer
by layer on the same input.

Departures, noted: the parameter tree is the program's, because the reference
must run on the SAME weights: ``runs`` (one stack for each run of (mixer,
expert layer) pairs, kernels input-major), ``experts`` EVERY expert layer's
held experts flat, the ``m``-th's from ``m * held``.  Weights are upcast to
float32 a part (an expert) at a time, so a 16 GB chip holds them in the dtype
they are served in; the softmax runs one query head at a time (a ``(s, s)``
score array: 0.26 GB at 8k tokens), the recurrence carries one sequence's
state and the head is applied at the compared rows alone: the same numbers.
``residual_in_fp32`` is false as published; the stream here is float32, as the
program's.  What the published config does not give (dtypes, initializers) is
the configuration file's ``assumed``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _relu2(x, up, down):
    return jnp.square(jax.nn.relu(x @ up)) @ down


@functools.partial(jax.jit, static_argnums=(2,))
def _mamba(h, w, consts):
    c = dict(consts)
    w = _f32({k: w[k] for k in ("ln1", "ssm_in", "conv", "dt_bias", "A_log", "D", "ssm_norm",
                                "ssm_out")})
    s = h.shape[0]
    heads, groups, n_state, taps = c["ssm_heads"], c["n_groups"], c["d_state"], c["d_conv"]
    d_ssm, gn = w["ssm_out"]["kernel"].shape[0], groups * n_state
    p = _rmsnorm(h, w["ln1"]["scale"], c["norm_eps"]) @ w["ssm_in"]["kernel"]
    z, xbc, dt = p[:, :d_ssm], p[:, d_ssm:2 * d_ssm + 2 * gn], p[:, 2 * d_ssm + 2 * gn:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(
        sum(padded[i:i + s] * w["conv"]["kernel"][i] for i in range(taps)) + w["conv"]["bias"])
    x = xbc[:, :d_ssm].reshape(s, heads, -1)
    b = xbc[:, d_ssm:d_ssm + gn].reshape(s, groups, n_state)
    cc = xbc[:, d_ssm + gn:].reshape(s, groups, n_state)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["A_log"])
    per = heads // groups  # head h reads group h // per

    def step(state, t):
        bt, ct = jnp.repeat(b[t], per, axis=0), jnp.repeat(cc[t], per, axis=0)
        state = jnp.exp(dt[t] * a)[:, None, None] * state \
            + (dt[t][:, None] * x[t])[:, :, None] * bt[:, None, :]
        return state, (state * ct[:, None, :]).sum(-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, x.shape[-1], n_state)), jnp.arange(s))
    y = (y + w["D"][:, None] * x).reshape(s, d_ssm) * jax.nn.silu(z)
    y = y.reshape(s, groups, -1)
    y = y / jnp.sqrt((y * y).mean(-1, keepdims=True) + c["norm_eps"])
    return h + (y.reshape(s, d_ssm) * w["ssm_norm"]["scale"]) @ w["ssm_out"]["kernel"]


@functools.partial(jax.jit, static_argnums=(2,))
def _attention(h, w, consts):
    c = dict(consts)
    w = _f32({k: w[k] for k in ("ln1", "q", "k", "v", "o")})
    s = h.shape[0]
    hq, hkv, e = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    u = _rmsnorm(h, w["ln1"]["scale"], c["norm_eps"])
    q = (u @ w["q"]["kernel"]).reshape(s, hq, e).transpose(1, 0, 2)      # (H, s, e)
    k = (u @ w["k"]["kernel"]).reshape(s, hkv, e).transpose(1, 0, 2)
    v = (u @ w["v"]["kernel"]).reshape(s, hkv, e).transpose(1, 0, 2)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_head(qi):
        qh, i = qi
        kh, vh = k[i // (hq // hkv)], v[i // (hq // hkv)]
        scores = (qh @ kh.T) / jnp.sqrt(jnp.float32(e))
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1) @ vh

    out = jax.lax.map(one_head, (q, jnp.arange(hq)))                     # (H, s, e)
    return h + out.transpose(1, 0, 2).reshape(s, hq * e) @ w["o"]["kernel"]


def route(y, router, consts: dict):
    """(chosen (s, top_k), weights (s, top_k), the 6th's selection score less
    the 7th's (s,), the 7th expert (s,)) over ALL the router's experts."""
    top_k = consts["experts_per_tok"]
    score = jax.nn.sigmoid(y @ router["kernel"].astype(jnp.float32))
    top, order = jax.lax.top_k(score + router["bias"].astype(jnp.float32), top_k + 1)
    chosen = order[:, :top_k]
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + consts["route_eps"]) \
        * consts["routed_scaling"]
    return chosen, weights, top[:, top_k - 1] - top[:, top_k], order[:, top_k]


def routed_part(y, router, experts, consts: dict):
    """What the held experts ``expert_offset .. + held`` add for the normed
    input ``y`` (s, d), each row's ``gap`` (the module's note) and which held
    experts it chose (s, held)."""
    offset, held = consts["expert_offset"], experts["up"].shape[0]
    chosen, weights, gap, runner_up = route(y, router, consts)

    def one_expert(out, e):
        w_e = (weights * (chosen == offset + e)).sum(-1, keepdims=True)
        mine = _f32(jax.tree_util.tree_map(lambda a: a[e], experts))
        return out + w_e * _relu2(y, mine["up"], mine["down"]), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), jnp.arange(held))
    is_held = lambda e: (e >= offset) & (e < offset + held)  # noqa: E731
    ours = is_held(chosen[:, -1]) | is_held(runner_up)
    mask = (chosen[:, :, None] == offset + jnp.arange(held)).any(axis=1)
    return out, jnp.where(ours, gap, jnp.inf), mask


def shared_part(y, shared):
    shared = _f32(shared)
    return _relu2(y, shared["up"], shared["down"])


@functools.partial(jax.jit, static_argnums=(3,))
def _experts(h, w, experts, consts):
    c = dict(consts)
    y = _rmsnorm(h, w["ln2"]["scale"].astype(jnp.float32), c["norm_eps"])
    routed, gap, mask = routed_part(y, w["router"], experts, c)
    return h + routed + shared_part(y, w["shared"]), routed, gap, mask


def _frozen(consts: dict) -> tuple:
    return tuple(sorted(consts.items()))


def forward(params: dict, tokens, consts: dict, taps: dict | None = None):
    """(the stream after the last layer (s, d), a list an expert layer of each
    token's ``gap`` (s,), and one of the held experts it chose (s, held)) of
    ONE sequence.  ``consts``: the configuration's numbers by the program's
    field names; ``taps``: the module's note (host arrays)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    frozen = _frozen(consts)
    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    n_moe = sum(run["ln2"]["scale"].shape[0] for run in params["runs"] if "ln2" in run)
    held = params["experts"]["up"].shape[0] // n_moe
    gaps, masks, m = [], [], 0
    with jax.default_matmul_precision("highest"):
        h = params["embed"]["tokens"][tokens].astype(jnp.float32)
        for run in params["runs"]:
            mixer = _mamba if "ssm_in" in run else _attention if "q" in run else None
            for i in range(jax.tree_util.tree_leaves(run)[0].shape[0]):
                w = at(run, i)
                if mixer is not None:
                    h = mixer(h, w, frozen)
                if "ln2" in w:
                    mine = jax.tree_util.tree_map(
                        lambda a: a[m * held:(m + 1) * held], params["experts"])
                    before = h
                    h, routed, gap, mask = _experts(h, w, mine, frozen)
                    if taps is not None:
                        taps.setdefault("layers", []).append(tuple(
                            np.asarray(a[taps["rows"]]) for a in (before, routed, gap)))
                    gaps.append(gap)
                    masks.append(mask)
                    m += 1
    return h, gaps, masks


def logits_at(params: dict, tokens, rows, consts: dict, taps: dict | None = None):
    """float32 logits (len(rows), vocab) of ONE sequence ``tokens`` at the
    positions ``rows``, from a full forward pass over the whole sequence
    (``taps``: ``forward``'s)."""
    h, _, _ = forward(params, tokens, consts, taps)
    with jax.default_matmul_precision("highest"):
        y = _rmsnorm(h[jnp.asarray(rows)], params["ln_f"]["scale"].astype(jnp.float32),
                     consts["norm_eps"])
        return y @ params["lm_head"]["kernel"].astype(jnp.float32)
