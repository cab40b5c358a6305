"""Plain reference for AFMoE (arcee-ai/Trinity-Large-Preview, ``model_type``
``afmoe``): the equations of Hugging Face's ``Afmoe*`` modules over a WHOLE
sequence.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no cache, no block table, no released block, no chunk, no
kernel, no tiles, nothing from ``ray_tpu``.  ``h`` the residual stream,
``RMSNorm`` with a learned scale at ``norm_eps``; no bias anywhere:

* ``h = E[token] * sqrt(d_model)`` (``mup_enabled``); a layer: ``h +=
  N2(Attn(N1(h)))``, then ``h += N4(FF(N3(h)))`` (``input_layernorm``,
  ``post_attention_layernorm``, ``pre_mlp_layernorm``, ``post_mlp_layernorm``);
  ``logits = N_f(h) W_head`` (the head is untied);
* Attn: ``q``, ``k``, ``v`` and the gate's logits ``g = x W_g``; ``q`` and
  ``k`` each RMSNorm'ed over their head's lanes with a learned scale; on a
  ``sliding_attention`` layer THEN the half-split rotary at ``rope_theta``
  (lanes ``[0 : e/2]`` turn with ``[e/2 : e]``: Hugging Face's
  ``rotate_half``), on a ``full_attention`` layer NOTHING; a dense causal
  softmax of ``q . k / sqrt(e)``, on a sliding layer over the keys ``j`` with
  ``i - j < window`` alone (a dense MASK: every key is there), query head
  ``i`` on key-value head ``i // (H / K)``; ``(o * sigmoid(g)) W_o``;
* FF of the first ``n_dense_layers``: ``(silu(y W_1) * (y W_3)) W_2``;
* FF of every later layer: ``p = sigmoid(y W_r)`` over ALL experts, the
  ``top_k`` largest of ``p + b`` chosen, ``w = p[chosen] / (sum p[chosen] +
  route_eps) * routed_scaling``; a plain loop over the held experts ``offset ..
  offset + held``, each on every token with the weight 0 where it was not
  chosen, plus the shared expert.  The part of an absent expert is left out
  (the program is given the same share).

**Routing is discontinuous.**  Choosing the 4 largest of 256 scores flips where
the 4th and the 5th lie closer than bfloat16 products upstream of the router
move them, both choices are this configuration's answer, and a flip takes a
held expert's part out of the layer's sum or puts one in.  ``forward`` returns
every position's ``margin``: the 4th selection score less the 5th, the smallest
over the expert layers (infinite where neither of the two experts is held:
such a flip is another chip's).  ``logits_at`` says nothing (a row all zero, on
which the harness's comparison reads 0) on a row whose margin is under
``margin``; the family passes ``ROUTING_MARGIN``
(``trinity-large-ep8-l5-1chip``'s ``correctness`` has the readings behind it; a
test holds the two equal).

Departures from the published modules, noted: the parameter tree is the
program's, because the reference must run on the SAME weights (``runs``: one
stack of layers for each run of one kind, kernels input-major; ``experts``
EVERY expert layer's held experts flat as ``gate`` / ``up`` / ``down``).  The
router's product is float32 at highest precision like every other (the
published module runs it in the model's dtype).  Weights are upcast to float32
layer by layer and expert by expert, the softmax runs one key-value head and
``QUERY_BLOCK`` queries at a time, and the expert loop ``TOKEN_BLOCK`` tokens at
a time, so that a 16 GB chip holds a 9k-token probe beside the weights in the
dtype they are served in: the same numbers.  What the published config does
not give (the gate, the QK-norm, no rotary on full layers, the embedding's
multiplier, the norms' places, dtypes, initializers) is the configuration
file's ``assumed``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: queries a block of the dense softmax, tokens a block of the expert loop
QUERY_BLOCK = 512
TOKEN_BLOCK = 2048
#: in units of the selection score ``p + b``
ROUTING_MARGIN = 0.004


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _rotary(x, theta):
    """x: (s, heads, e) at positions 0 .. s - 1."""
    s, e = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, e, 2, dtype=jnp.float32) / e)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _attention(h, w, sliding: bool, consts):
    c = dict(consts)
    w = _f32({k: w[k] for k in ("ln1", "ln2", "q", "k", "v", "gate", "o", "q_norm", "k_norm")})
    s = h.shape[0]
    hq, hkv, e = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    u = _rmsnorm(h, w["ln1"]["scale"], c["norm_eps"])
    q = _rmsnorm((u @ w["q"]["kernel"]).reshape(s, hq, e), w["q_norm"]["scale"], c["norm_eps"])
    k = _rmsnorm((u @ w["k"]["kernel"]).reshape(s, hkv, e), w["k_norm"]["scale"], c["norm_eps"])
    if sliding:
        q, k = _rotary(q, c["rope_theta"]), _rotary(k, c["rope_theta"])
    v = (u @ w["v"]["kernel"]).reshape(s, hkv, e)
    g = u @ w["gate"]["kernel"]
    # whole blocks of queries; the padding attends like the last token and is cut
    block = min(QUERY_BLOCK, s)
    n_blocks = -(-s // block)
    q = jnp.pad(q, ((0, n_blocks * block - s), (0, 0), (0, 0)))
    q = q.reshape(n_blocks, block, hkv, hq // hkv, e)
    at = jnp.arange(n_blocks * block).reshape(n_blocks, block)

    def one_kv_head(kv):
        kh, vh, qh = kv                               # (s, e), (s, e), (blocks, block, G, e)

        def one_block(qb):
            qg, pos = qb                              # (block, G, e), (block,)
            scores = jnp.einsum("sge,te->gst", qg, kh) / jnp.sqrt(jnp.float32(e))
            seen = pos[:, None] >= jnp.arange(s)[None, :]
            if sliding:
                seen &= pos[:, None] - jnp.arange(s)[None, :] < c["window"]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("gst,te->sge", probs, vh)

        return jax.lax.map(one_block, (qh, at))       # (blocks, block, G, e)

    out = jax.lax.map(one_kv_head, (
        k.transpose(1, 0, 2), v.transpose(1, 0, 2), q.transpose(2, 0, 1, 3, 4)))
    # (K, blocks, block, G, e) -> (s, K, G, e)
    out = out.reshape(hkv, n_blocks * block, hq // hkv, e)[:, :s].transpose(1, 0, 2, 3)
    gated = out.reshape(s, hq * e) * jax.nn.sigmoid(g)
    return h + _rmsnorm(gated @ w["o"]["kernel"], w["ln2"]["scale"], c["norm_eps"])


@functools.partial(jax.jit, static_argnums=(2,))
def _dense(h, w, consts):
    c = dict(consts)
    w = _f32({k: w[k] for k in ("ln3", "ln4", "mlp")})
    y = _rmsnorm(h, w["ln3"]["scale"], c["norm_eps"])
    out = _swiglu(y, w["mlp"]["gate"], w["mlp"]["up"], w["mlp"]["down"])
    return h + _rmsnorm(out, w["ln4"]["scale"], c["norm_eps"])


@functools.partial(jax.jit, static_argnums=(3,))
def _experts(h, w, experts, consts):
    """Returns (h', held (s, held) bool: which held experts each token chose,
    margin (s,): the module's note)."""
    c = dict(consts)
    top_k, offset = c["experts_per_tok"], c["expert_offset"]
    held, s = experts["gate"].shape[0], h.shape[0]
    y = _rmsnorm(h, w["ln3"]["scale"].astype(jnp.float32), c["norm_eps"])
    p = jax.nn.sigmoid(y @ w["router"]["kernel"].astype(jnp.float32))
    top, order = jax.lax.top_k(p + w["router"]["bias"].astype(jnp.float32), top_k + 1)
    chosen = order[:, :top_k]
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + c["route_eps"]) * c["routed_scaling"]
    wmat = (weights[:, :, None] * (chosen[:, :, None] == offset + jnp.arange(held))).sum(1)

    # a block of tokens through every held expert, one expert at a time
    block = min(TOKEN_BLOCK, s)
    n_blocks = -(-s // block)
    pad = n_blocks * block - s
    yb = jnp.pad(y, ((0, pad), (0, 0))).reshape(n_blocks, block, -1)
    wb = jnp.pad(wmat, ((0, pad), (0, 0))).reshape(n_blocks, block, held)

    def one_expert(out, e):
        mine = _f32(jax.tree_util.tree_map(lambda a: a[e], experts))
        part = jax.lax.map(
            lambda b: b[1][:, e, None] * _swiglu(b[0], mine["gate"], mine["up"], mine["down"]),
            (yb, wb))
        return out + part, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(yb), jnp.arange(held))
    sh = _f32(w["shared"])
    out = out.reshape(n_blocks * block, -1)[:s] + _swiglu(y, sh["gate"], sh["up"], sh["down"])
    is_held = lambda e: (e >= offset) & (e < offset + held)  # noqa: E731
    ours = is_held(order[:, top_k - 1]) | is_held(order[:, top_k])
    margin = jnp.where(ours, top[:, top_k - 1] - top[:, top_k], jnp.inf)
    normed = _rmsnorm(out, w["ln4"]["scale"].astype(jnp.float32), c["norm_eps"])
    return h + normed, wmat > 0, margin


def _frozen(consts: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in consts.items()))


def forward(params: dict, tokens, consts: dict):
    """(the stream after the last layer (s, d); a list per EXPERT layer of (s,
    held) bool, which held experts each token chose; (s,) each position's
    margin, the smallest over the expert layers) of ONE sequence.  ``consts``:
    the configuration's numbers by the program's field names
    (``layer_types`` among them: which layers slide)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    frozen = _frozen({k: v for k, v in consts.items() if k != "layer_types"})
    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    n_expert_layers = sum(
        run["ln1"]["scale"].shape[0] for run in params["runs"] if "router" in run)
    held = params["experts"]["gate"].shape[0] // max(n_expert_layers, 1)
    masks, margin, index, layer = [], jnp.full(tokens.shape, jnp.inf), 0, 0
    with jax.default_matmul_precision("highest"):
        h = params["embed"]["tokens"][tokens].astype(jnp.float32) * consts["d_model"] ** 0.5
        for run in params["runs"]:
            for i in range(run["ln1"]["scale"].shape[0]):
                w = at(run, i)
                h = _attention(h, w, consts["layer_types"][layer] == "sliding_attention", frozen)
                layer += 1
                if "mlp" in run:
                    h = _dense(h, w, frozen)
                    continue
                mine = jax.tree_util.tree_map(
                    lambda a: a[index * held:(index + 1) * held], params["experts"])
                h, mask, near = _experts(h, w, mine, frozen)
                masks.append(mask)
                margin = jnp.minimum(margin, near)
                index += 1
    return h, masks, margin


def logits_and_margins(params: dict, tokens, rows, consts: dict):
    """(float32 logits (len(rows), vocab), each row's margin (len(rows),)) of
    ONE sequence ``tokens`` at the positions ``rows``, from a full forward
    pass over the whole sequence."""
    h, _, margins = forward(params, tokens, consts)
    rows = jnp.asarray(rows)
    with jax.default_matmul_precision("highest"):
        y = _rmsnorm(h[rows], params["ln_f"]["scale"].astype(jnp.float32), consts["norm_eps"])
        return y @ params["lm_head"]["kernel"].astype(jnp.float32), margins[rows]


def logits_at(params: dict, tokens, rows, consts: dict, margin: float = 0.0):
    """The logits at ``rows``; a row whose own routing lies within ``margin``
    of a flip in some expert layer is all zero (the module's note)."""
    logits, margins = logits_and_margins(params, tokens, rows, consts)
    return jnp.where((margins >= margin)[:, None], logits, 0.0)
