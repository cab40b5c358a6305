"""LFM2-MoE (LiquidAI/LFM2-24B-A2B, ``model_type`` ``lfm2_moe``) as the first
stage of a pipeline serves it: gated short-convolution mixers with a
grouped-query attention layer of 64-WIDE heads among every four, two leading
dense gated MLPs and then an expert layer whose 64 experts ALL lie on this
chip.

``h`` is the float32 residual stream, ``RMSNorm`` has a learned scale; no bias
anywhere, no residual or logit multiplier::

    h   = E[token]
    h  += Mixer(RMSNorm_op(h))
    h  += FF(RMSNorm_ffn(h))
    logits = RMSNorm_f(h) E^T                          (the head is tied)

* **Mixer, a ``conv`` layer** (``ops.short_conv``): ``[B | C | x] = u W_in``;
  ``s = B * x``; a depthwise causal convolution of ``conv_taps`` taps over
  ``s``, no activation; ``(C * c) W_out``.  A sequence carries the last
  ``conv_taps - 1`` gated inputs.
* **Mixer, a ``full_attention`` layer**: ``q`` (``H`` heads of ``e``), ``k``,
  ``v`` (``K`` heads); ``q`` and ``k`` each RMSNorm'ed over their ``e`` with a
  learned scale, THEN the half-split rotary at ``rope_theta``; causal softmax
  of ``q . k / sqrt(e)`` in float32, ``H / K`` query heads a key-value head;
  ``W_o``.  ``e`` is 64, half a lane row: the pool holds ``KV_PACK`` = 2
  key-value heads side by side in a row of 128 (``ops.gqa_attention``), a
  token's K and V unpadded.
* **FF of the first ``n_dense_layers``**: ``(silu(y W_1) * (y W_3)) W_2`` at
  ``d_ff``.  **FF of every later layer**: ``p = sigmoid(y W_r)`` over ALL
  ``n_routed_experts`` in float32; the ``experts_per_tok`` largest of ``p + b``
  chosen (``b`` chooses and does not weigh); ``w = p[chosen] / (sum p[chosen]
  + route_eps) * routed_scaling`` (``ops.moe.route``); ``sum over chosen AND
  held e of w_e Expert_e(y)`` at ``d_expert``, droplessly, no shared expert.
  This chip holds experts ``expert_offset .. expert_offset + experts_held``:
  at the published deployment ALL of them (``expert_parallel`` 1), and a share
  of the layer is one configuration away.

What a sequence holds on the device (``llm.cache.HybridPool``) is split by
layer kind: the tails of every ``conv`` layer in ONE state leaf, blocks of K
and V in the attention layers.  The layer loop is ``blocks.pattern_layers``:
one ``_carry_loop`` a RUN of layers of one (mixer, feed-forward) kind
(``runs()``: at the first ten published layers two dense conv layers, then
attention, three conv, attention, three conv, all with experts); the experts
of every EXPERT layer lie in one flat array from the first expert layer on.
The paged K/V step and the pattern loop are ``models.blocks``', the routed
layer's ledger ``ops.moe``'s (``counters`` is what the programs count on the
device, ``stats()["moe"]``); the short convolution, the packed heads, the
QK-norm and the pattern are HERE.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.blocks import (
    check_share, dot32, gated_mlp_init, last_valid, normal_layers, paged_kv_chunk,
    paged_kv_decode, pattern_layers, pattern_of, rmsnorm, runs_of)
from ray_tpu.ops.gqa_attention import rotary_half
from ray_tpu.ops.moe import COUNTERS  # noqa: F401  benchmark/families/lfm2_moe.py imports it
from ray_tpu.ops.moe import (
    count_routed, counters_shape, expert_layer, held_pairs, read_counters, route, swiglu)
from ray_tpu.ops.short_conv import short_conv_chunk, short_conv_decode

#: key-value heads side by side in one row of the pool: two heads of 64 fill
#: the 128 lanes the paged kernel's rows have
KV_PACK = 2
#: the published pattern's first ten layers
FIRST_TEN = ("conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
             "conv", "conv", "conv")


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    seq_len: int = 128000
    d_model: int = 2048
    #: the published 40 cut to the first pipeline stage's 10; ``layer_types``
    #: names each layer's mixer, the first ``n_dense_layers`` close with a
    #: dense MLP and the others with the expert layer
    n_layers: int = 10
    layer_types: tuple = FIRST_TEN
    n_dense_layers: int = 2
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    #: taps of the short convolution (the published ``conv_L_cache``)
    conv_taps: int = 3
    d_ff: int = 11776
    d_expert: int = 1536
    #: the router's width, as published; of them this chip holds
    #: ``experts_held`` from ``expert_offset``, one of ``expert_parallel``
    #: chips that share each layer
    n_routed_experts: int = 64
    experts_held: int = 64
    expert_offset: int = 0
    expert_parallel: int = 1
    experts_per_tok: int = 4
    routed_scaling: float = 1.0
    route_eps: float = 1e-6
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    #: the initializer's spread of the embedding, of the attention scores
    #: (through the query norm's scale), what ``W_o`` and the experts' ``W_2``
    #: are scaled by, how much of an expert is its OWN (the rest is one expert
    #: its layer's experts share), and the range of the convolution's taps
    init_range: float = 0.02
    score_spread: float = 4.0
    attn_out_gain: float = 4.0
    expert_out_gain: float = 1.0
    expert_own_share: float = 1.0
    tap_range: float = 2.0
    dtype: str = "bfloat16"
    attn_impl: str = "auto"

    #: what a sequence holds on the device (``llm.engine``): blocks of the
    #: attention layers' K/V AND a slot of the conv layers' tails
    cache_kind = "hybrid"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", pattern_of(
            self.layer_types, self.n_layers, ("conv", "full_attention")))
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError("the dense layers lead, and an expert layer follows them")
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % KV_PACK:
            raise ValueError("query heads and packed key-value heads come in whole groups")
        check_share(
            self.n_routed_experts, self.expert_offset, self.experts_held, self.experts_per_tok)

    def n_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    def runs(self) -> tuple:
        """The layers as runs of one kind: ``((mixer, feed-forward, how
        many), ...)``, the feed-forward ``dense`` or ``moe``."""
        kinds = [(mixer, "dense" if i < self.n_dense_layers else "moe")
                 for i, mixer in enumerate(self.layer_types)]
        return tuple((*kind, n) for kind, n in runs_of(kinds))

    def serving_body(self) -> "Lfm2MoeBody":
        return Lfm2MoeBody(self)


def lfm2_moe_init(rng: jax.Array, cfg: Lfm2MoeConfig) -> dict:
    """Seeded random parameters, made IN ``cfg.dtype`` a layer (an expert) at
    a time (float32 masters of 5.27B parameters would be 21 GB).
    ``params["runs"][i]`` holds run ``i``'s layers stacked (mixer, both norms,
    and its dense MLP or its router), ``params["experts"]`` EVERY expert
    layer's held experts flat, the ``m``-th expert layer's from ``m *
    experts_held``.

    Every projection normal at ``fan_in ** -0.5``, the router's too (on a
    normed input its logits are about N(0, 1): near-uniform routing), norm
    scales 1, but for what the configuration file's ``assumed`` explains: the
    embedding normal at ``init_range`` (the head is tied: a large one repeats
    its input); the QUERY norm's scale at ``score_spread`` (normed ``q`` and
    ``k`` score ``q . k / sqrt(e)`` at a spread of exactly 1, whatever ``W_q``
    and ``W_k`` are: the softmax is then near the values' mean and nothing
    the K/V cache holds reaches the logits; the norm's learned scale is where
    a trained model sets its scores' spread); ``W_o`` times ``attn_out_gain``
    (two attention layers among ten); the taps uniform in ``+- tap_range`` (at 2
    a conv mixer adds about two units, two thirds of them from the tails); the
    experts of a layer AKIN (``expert_own_share``, ``akin`` below) and their
    ``W_2`` times ``expert_out_gain`` (four near-equal experts a token: a
    flipped routing choice, which bfloat16 products upstream of the router
    make on most rows, swaps a quarter of the layer's output for another
    expert's, and between unrelated experts that outweighs keys, values or
    tails held at 3 bits; between akin ones it swaps ``own`` of that, so the
    layer can be as loud as a mixer and a fault in it reach the logits).  The
    selection bias is zero: the router's random logits spread the load evenly
    already."""
    d, dt, e = cfg.d_model, jnp.dtype(cfg.dtype), cfg.head_dim
    hq, hkv = cfg.n_heads * e, cfg.n_kv_heads * e

    normal = functools.partial(normal_layers, dtype=dt)
    mlp = functools.partial(gated_mlp_init, d=d)

    def akin(key, n: int, shape: tuple, std: float):
        """``normal`` for the experts: the ``experts_held`` of a layer are
        ``sqrt(1 - own ** 2)`` of ONE matrix the layer draws and ``own`` of a
        matrix of their own (at 1 unrelated, as ``normal`` makes them)."""
        own, held = cfg.expert_own_share, cfg.experts_held

        def layer(k):
            k_shared, k_own = jax.random.split(k)
            shared = jax.random.normal(k_shared, shape, jnp.float32) * (1 - own * own) ** 0.5
            return jax.lax.map(
                lambda k: ((shared + own * jax.random.normal(k, shape, jnp.float32))
                           * std).astype(dt),
                jax.random.split(k_own, held))

        return jax.lax.map(layer, jax.random.split(key, n // held)).reshape(n, *shape)

    def closing(key, n: int, ff: str) -> dict:
        out = {"ln1": {"scale": jnp.ones((n, d), dt)}, "ln2": {"scale": jnp.ones((n, d), dt)}}
        if ff == "dense":
            return dict(out, mlp=mlp(key, n, width=cfg.d_ff, make=normal))
        return dict(out, router={
            "kernel": normal(key, n, (d, cfg.n_routed_experts), d**-0.5),
            "bias": jnp.zeros((n, cfg.n_routed_experts), jnp.float32)})

    def conv(key, n: int, ff: str) -> dict:
        ks = jax.random.split(key, 4)
        taps = jax.random.uniform(
            ks[1], (n, cfg.conv_taps, d), jnp.float32, -cfg.tap_range, cfg.tap_range)
        return dict(
            closing(ks[0], n, ff),
            conv_in={"kernel": normal(ks[2], n, (d, 3 * d), d**-0.5)},
            conv={"kernel": taps.astype(dt)},
            conv_out={"kernel": normal(ks[3], n, (d, d), d**-0.5)},
        )

    def attention(key, n: int, ff: str) -> dict:
        ks = jax.random.split(key, 5)
        return dict(
            closing(ks[0], n, ff),
            q={"kernel": normal(ks[1], n, (d, hq), d**-0.5)},
            k={"kernel": normal(ks[2], n, (d, hkv), d**-0.5)},
            v={"kernel": normal(ks[3], n, (d, hkv), d**-0.5)},
            o={"kernel": normal(ks[4], n, (hq, d), hq**-0.5 * cfg.attn_out_gain)},
            q_norm={"scale": jnp.full((n, e), cfg.score_spread, dt)},
            k_norm={"scale": jnp.ones((n, e), dt)},
        )

    runs = cfg.runs()
    ks = jax.random.split(rng, len(runs) + 2)
    made = {"conv": conv, "full_attention": attention}
    return {
        "embed": {"tokens": normal(ks[0], 1, (cfg.vocab_size, d), cfg.init_range)[0]},
        "runs": [made[mixer](k, n, ff) for k, (mixer, ff, n) in zip(ks[2:], runs)],
        "experts": mlp(ks[1], cfg.n_expert_layers * cfg.experts_held, width=cfg.d_expert,
                       make=akin, out_gain=cfg.expert_out_gain),
        "ln_f": {"scale": jnp.ones((d,), dt)},
    }


class Lfm2MoeBody:
    """The family's traced layer programs for ``HybridModelRunner``.
    ``arrays`` is ``(k, v, tails, counters)``: K and V ``(attention layers,
    blocks, K / KV_PACK, block, KV_PACK * e)``, the convolutions' tails
    ``(conv layers, slots + 1, conv_taps - 1, d)`` and the device's own counts
    (``ops.moe.counters_shape``).  A table row is ``[slot,
    block table...]``, slot 0 and block 0 the trash a dead decode row and a
    padded chunk row write; a dead row has no pair in the expert layer and
    counts nowhere."""

    def __init__(self, cfg: Lfm2MoeConfig):
        self.cfg = cfg
        self.dt = jnp.dtype(cfg.dtype)

    # -- what the pools hold ----------------------------------------------

    def kv_layout(self) -> dict:
        """The paged pool: the ATTENTION layers' K and V, ``KV_PACK`` heads a
        row: a token's bytes are its heads', unpadded."""
        cfg = self.cfg
        return {"n_layers": cfg.n_of("full_attention"),
                "n_heads": cfg.n_kv_heads // KV_PACK,
                "head_dim": KV_PACK * cfg.head_dim, "dtype": cfg.dtype}

    def state_leaves(self, block_size: int) -> dict:
        """name -> (layers, one slot's shape, dtype): the CONV layers' tails."""
        cfg = self.cfg
        return {"tails": (cfg.n_of("conv"), (cfg.conv_taps - 1, cfg.d_model), cfg.dtype)}

    def counters(self) -> tuple:
        return counters_shape(self.cfg.experts_held)

    read_counters = staticmethod(read_counters)

    # -- shared layer math --------------------------------------------------

    def embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["embed"]["tokens"][tokens].astype(jnp.float32)

    def lm_head(self, params, h):
        """The tied head: the embedding's rows against the normed stream."""
        with jax.named_scope("lm_head"):
            y = rmsnorm(h, params["ln_f"]["scale"], self.cfg.norm_eps).astype(self.dt)
            return jnp.einsum("nd,vd->nv", y, params["embed"]["tokens"].astype(self.dt),
                              preferred_element_type=jnp.float32)

    def _norm(self, h, layer, which: str):
        return rmsnorm(h, layer[which]["scale"], self.cfg.norm_eps)

    def _conv_in(self, h, layer):
        """The input projection and the first gate: (the gated input ``B *
        x`` in the compute dtype, ``C`` float32), each (n, d)."""
        d = self.cfg.d_model
        p = dot32(self._norm(h, layer, "ln1").astype(self.dt), layer["conv_in"]["kernel"])
        return (p[:, :d] * p[:, 2 * d:]).astype(self.dt), p[:, d:2 * d]

    def _conv_out(self, h, layer, gate, c):
        return h + dot32((gate * c).astype(self.dt), layer["conv_out"]["kernel"])

    def _qkv(self, h, layer, positions):
        """q (n, H, e), k (n, K, e) float32, each normed over its ``e`` and
        THEN rotated at ``positions``; v (n, K, e) in the compute dtype."""
        cfg, n = self.cfg, h.shape[0]
        with jax.named_scope("qkv"):
            a = self._norm(h, layer, "ln1").astype(self.dt)
            q = dot32(a, layer["q"]["kernel"]).reshape(n, cfg.n_heads, cfg.head_dim)
            k = dot32(a, layer["k"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            v = dot32(a, layer["v"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            q = rotary_half(rmsnorm(q, layer["q_norm"]["scale"], cfg.norm_eps),
                            positions, cfg.rope_theta)
            k = rotary_half(rmsnorm(k, layer["k_norm"]["scale"], cfg.norm_eps),
                            positions, cfg.rope_theta)
            return q, k, v.astype(self.dt)

    def _packed(self, x):
        """(n, K, e) -> (n, K / KV_PACK, KV_PACK * e) in the pool's dtype: the
        same numbers in the same order."""
        cfg = self.cfg
        return x.astype(self.dt).reshape(
            x.shape[0], cfg.n_kv_heads // KV_PACK, KV_PACK * cfg.head_dim)

    def _attn_out(self, h, layer, att):
        with jax.named_scope("attn_out"):
            return h + dot32(att.astype(self.dt).reshape(h.shape[0], -1), layer["o"]["kernel"])

    def _dense_mlp(self, h, layer):
        with jax.named_scope("dense_mlp"):
            y, w = self._norm(h, layer, "ln2").astype(self.dt), layer["mlp"]
            return h + swiglu(y, w["gate"], w["up"], w["down"])

    def _expert_mlp(self, h, layer, live, counts, phase: str, experts, index):
        """The expert layer's part this chip holds.  ``counts`` (``ops.moe``'s
        ledger) gets this layer through ``count_routed``.  ``experts``: the
        held experts of every expert layer, flat, this layer's from ``index *
        experts_held``."""
        cfg = self.cfg
        with jax.named_scope("moe_router"):
            y32 = self._norm(h, layer, "ln2")
            chosen, weights = route(
                y32, layer["router"]["kernel"], layer["router"]["bias"], cfg.experts_per_tok,
                cfg.routed_scaling, eps=cfg.route_eps)
            mask, wmat = held_pairs(chosen, weights, cfg.expert_offset, cfg.experts_held, live)
            counts = count_routed(counts, mask, phase)
        with jax.named_scope("moe_experts"):
            return h + expert_layer(
                y32.astype(self.dt), mask, wmat, experts["gate"], experts["up"], experts["down"],
                first=index * cfg.experts_held, top_k=cfg.experts_per_tok,
                impl=cfg.attn_impl), counts

    def _layers(self, params, x, arrays, live, phase: str, positions, slots, conv_step, attend):
        """``blocks.pattern_layers`` over the runs: a step's layers, written
        once for both steps.  ``conv_step(tails, s, taps, at)`` is the step's
        short convolution (``ops.short_conv``), ``attend`` its paged K/V step,
        ``slots`` its slot(s) of tails, ``live`` its rows that count."""
        n_blocks, n_slots, experts = arrays[0].shape[1], arrays[2].shape[1], params["experts"]

        def conv(h, layer, k_pool, v_pool, tails, l):
            with jax.named_scope("short_conv"):
                s, gate = self._conv_in(h, layer)
                with jax.named_scope("conv_update"):
                    tails, c = conv_step(tails, s, layer["conv"]["kernel"], l * n_slots + slots)
                return self._conv_out(h, layer, gate, c), k_pool, v_pool, tails

        def attention(h, layer, k_pool, v_pool, tails, l):
            base = l * n_blocks
            q, k, v = self._qkv(h, layer, positions)
            att, k_pool, v_pool = attend(q, k, v, k_pool, v_pool, base)
            return self._attn_out(h, layer, att), k_pool, v_pool, tails

        closings = {
            "dense": lambda h, layer, counts, m: (self._dense_mlp(h, layer), counts),
            "moe": lambda h, layer, counts, m: self._expert_mlp(
                h, layer, live, counts, phase, experts, m)}
        return pattern_layers(
            self.cfg.runs(), params["runs"], x, arrays,
            {"conv": conv, "full_attention": attention}, closings, phase)

    def decode(self, params, x, arrays, positions, tables):
        """One token of many sequences.  x: (S, d) embedded tokens at
        ``positions``; tables: (S, 1 + T).  Returns (hidden (S, d), arrays)."""
        slots, btab = tables[:, 0], tables[:, 1:]
        attend = paged_kv_decode(arrays[0], btab, positions, self.cfg.attn_impl, self._packed)
        return self._layers(
            params, x, arrays, slots > 0, "decode", positions, slots, short_conv_decode, attend)

    def chunk(self, params, x, arrays, start, n_valid, table):
        """A prefill chunk.  x: (C, d) embedded tokens of ONE sequence at
        ``start ..``, the first ``n_valid`` real; table: (1 + T,).  Returns
        (the last valid token's hidden (1, d), arrays)."""
        slot, btab = table[0], table[1:]
        positions = start + jnp.arange(x.shape[0], dtype=jnp.int32)
        attend = paged_kv_chunk(arrays[0], btab, positions, start, n_valid, self._packed)
        conv_step = lambda tails, s, taps, at: short_conv_chunk(  # noqa: E731
            tails, s, taps, at, start == 0, n_valid)
        x, arrays = self._layers(
            params, x, arrays, jnp.arange(x.shape[0]) < n_valid, "chunk", positions, slot,
            conv_step, attend)
        return last_valid(x, n_valid), arrays
