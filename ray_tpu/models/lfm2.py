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
and V in the attention layers.  The layer loop is one ``_carry_loop`` a RUN of
layers of one (mixer, feed-forward) kind (``runs()``: at the first ten
published layers two dense conv layers, then attention, three conv, attention,
three conv, all with experts); the experts of every EXPERT layer lie in one
flat array from the first expert layer on.  ``counters`` is what the programs
count on the device: Granite-4.0-H's seven names, and a chunk's touched
experts, computed rows and grouped-form steps beside its pairs.
"""

from __future__ import annotations

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.model_runner import _carry_loop, _chunk_write, _slots_write
from ray_tpu.ops.gqa_attention import (
    gqa_chunk_attention, gqa_paged_attention, rotary_half)
from ray_tpu.ops.moe import (
    batch_steps, expert_layer, grouped_steps, held_pairs, route, swiglu, tile_rows)
from ray_tpu.ops.short_conv import short_conv_chunk, short_conv_decode

#: ``stats()["moe"]``: the scalar counters, then ``load`` (one a held expert)
COUNTERS = ("decode_pairs", "decode_touched", "decodes", "chunk_pairs", "chunks",
            "decode_tile_rows", "decode_expert_steps", "chunk_touched", "chunk_tile_rows",
            "chunk_expert_steps")
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
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.n_layers or set(self.layer_types) != {
                "conv", "full_attention"}:
            raise ValueError(
                "layer_types names n_layers mixers, 'conv' and 'full_attention' both")
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError("the dense layers lead, and an expert layer follows them")
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % KV_PACK:
            raise ValueError("query heads and packed key-value heads come in whole groups")
        if self.expert_offset + self.experts_held > self.n_routed_experts:
            raise ValueError("the held experts lie outside the router's width")
        if self.experts_per_tok > self.n_routed_experts:
            raise ValueError("more experts a token than the router has")

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
        return tuple((*kind, len(list(g))) for kind, g in itertools.groupby(kinds))

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

    def normal(key, n: int, shape: tuple, std: float):
        """(n,) + shape, one layer at a time."""
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape, jnp.float32) * std).astype(dt),
            jax.random.split(key, n))

    def mlp(key, n: int, width: int, make=normal, out_gain: float = 1.0) -> dict:
        ks = jax.random.split(key, 3)
        return {"gate": make(ks[0], n, (d, width), d**-0.5),
                "up": make(ks[1], n, (d, width), d**-0.5),
                "down": make(ks[2], n, (width, d), width**-0.5 * out_gain)}

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
            return dict(out, mlp=mlp(key, n, cfg.d_ff))
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
        "experts": mlp(ks[1], cfg.n_expert_layers * cfg.experts_held, cfg.d_expert, akin,
                       cfg.expert_out_gain),
        "ln_f": {"scale": jnp.ones((d,), dt)},
    }


def _rmsnorm(x, scale, eps):
    """RMSNorm in float32 over the last axis."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _dot32(x, kernel):
    """x @ kernel on x's dtype, float32 out."""
    return jnp.dot(x, kernel.astype(x.dtype), preferred_element_type=jnp.float32)


class Lfm2MoeBody:
    """The family's traced layer programs for ``HybridModelRunner``.
    ``arrays`` is ``(k, v, tails, counters)``: K and V ``(attention layers,
    blocks, K / KV_PACK, block, KV_PACK * e)``, the convolutions' tails
    ``(conv layers, slots + 1, conv_taps - 1, d)`` and the device's own counts
    ``(1, len(COUNTERS) + experts_held)`` int32.  A table row is ``[slot,
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
        """Shapes and dtypes of what the steps carry beside the pools."""
        return (jax.ShapeDtypeStruct((1, len(COUNTERS) + self.cfg.experts_held), jnp.int32),)

    @staticmethod
    def read_counters(arrays) -> dict:
        """``stats()``'s part from the fetched counters: ``{"moe": ...}``."""
        flat = np.asarray(arrays[0]).reshape(-1)
        out = {name: int(flat[i]) for i, name in enumerate(COUNTERS)}
        out["load"] = [int(x) for x in flat[len(COUNTERS):]]
        return {"moe": out}

    # -- shared layer math --------------------------------------------------

    def embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["embed"]["tokens"][tokens].astype(jnp.float32)

    def lm_head(self, params, h):
        """The tied head: the embedding's rows against the normed stream."""
        with jax.named_scope("lm_head"):
            y = _rmsnorm(h, params["ln_f"]["scale"], self.cfg.norm_eps).astype(self.dt)
            return jnp.einsum("nd,vd->nv", y, params["embed"]["tokens"].astype(self.dt),
                              preferred_element_type=jnp.float32)

    def _norm(self, h, layer, which: str):
        return _rmsnorm(h, layer[which]["scale"], self.cfg.norm_eps)

    def _conv_in(self, h, layer):
        """The input projection and the first gate: (the gated input ``B *
        x`` in the compute dtype, ``C`` float32), each (n, d)."""
        d = self.cfg.d_model
        p = _dot32(self._norm(h, layer, "ln1").astype(self.dt), layer["conv_in"]["kernel"])
        return (p[:, :d] * p[:, 2 * d:]).astype(self.dt), p[:, d:2 * d]

    def _conv_out(self, h, layer, gate, c):
        return h + _dot32((gate * c).astype(self.dt), layer["conv_out"]["kernel"])

    def _qkv(self, h, layer, positions):
        """q (n, H, e), k (n, K, e) float32, each normed over its ``e`` and
        THEN rotated at ``positions``; v (n, K, e) in the compute dtype."""
        cfg, n = self.cfg, h.shape[0]
        with jax.named_scope("qkv"):
            a = self._norm(h, layer, "ln1").astype(self.dt)
            q = _dot32(a, layer["q"]["kernel"]).reshape(n, cfg.n_heads, cfg.head_dim)
            k = _dot32(a, layer["k"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            v = _dot32(a, layer["v"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            q = rotary_half(_rmsnorm(q, layer["q_norm"]["scale"], cfg.norm_eps),
                            positions, cfg.rope_theta)
            k = rotary_half(_rmsnorm(k, layer["k_norm"]["scale"], cfg.norm_eps),
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
            return h + _dot32(att.astype(self.dt).reshape(h.shape[0], -1), layer["o"]["kernel"])

    def _dense_mlp(self, h, layer):
        with jax.named_scope("dense_mlp"):
            y, w = self._norm(h, layer, "ln2").astype(self.dt), layer["mlp"]
            return h + swiglu(y, w["gate"], w["up"], w["down"])

    def _expert_mlp(self, h, layer, live, counts, phase: str, experts, index):
        """The expert layer's part this chip holds.  ``counts`` gets this
        layer's pairs, touched experts, computed rows and expert steps under
        ``<phase>_*`` (a decode's steps are the batch form's, a chunk's the
        grouped form's: none where the other form ran) and its load by held
        expert.
        ``experts``: the held experts of every expert layer, flat, this
        layer's from ``index * experts_held``."""
        cfg = self.cfg
        with jax.named_scope("moe_router"):
            y32 = self._norm(h, layer, "ln2")
            chosen, weights = route(
                y32, layer["router"]["kernel"], layer["router"]["bias"], cfg.experts_per_tok,
                cfg.routed_scaling, eps=cfg.route_eps)
            mask, wmat = held_pairs(chosen, weights, cfg.expert_offset, cfg.experts_held, live)
            load = mask.sum(axis=0).astype(jnp.int32)
            counts = counts.at[len(COUNTERS):].add(load)
            for name, n in (("pairs", load.sum()), ("touched", (load > 0).sum()),
                            ("tile_rows", tile_rows(load, mask.shape[0]))):
                counts = counts.at[COUNTERS.index(f"{phase}_{name}")].add(n.astype(jnp.int32))
            steps = batch_steps if phase == "decode" else grouped_steps
            counts = counts.at[COUNTERS.index(f"{phase}_expert_steps")].add(
                steps(load, mask.shape[0]))
        with jax.named_scope("moe_experts"):
            return h + expert_layer(
                y32.astype(self.dt), mask, wmat, experts["gate"], experts["up"], experts["down"],
                first=index * cfg.experts_held, top_k=cfg.experts_per_tok,
                impl=cfg.attn_impl), counts

    def _layers(self, params, x, arrays, mixers: dict, live, phase: str):
        """One ``_carry_loop`` a run of layers of one (mixer, feed-forward)
        kind, each over ALL the pools (a run leaves the other kind's as they
        came).  ``mixers[kind](h, layer, k, v, tails, l)`` is the step's mixer
        of the ``l``-th layer of that kind and gives ``(h, k, v, tails)``."""
        n_blocks, experts = arrays[0].shape[1], params["experts"]
        seen, expert_layers = {"conv": 0, "full_attention": 0}, 0
        for (kind, ff, n), run in zip(self.cfg.runs(), params["runs"]):

            def layer_fn(h, layer, k, v, tails, counts, base, mix=mixers[kind], ff=ff,
                         first=seen[kind], index=expert_layers):
                at = base // n_blocks  # the layer's place in its run
                h, k, v, tails = mix(h, layer, k, v, tails, first + at)
                if ff == "dense":
                    h = self._dense_mlp(h, layer)
                else:
                    h, counts = self._expert_mlp(
                        h, layer, live, counts, phase, experts, index + at)
                return h, k, v, tails, counts

            x, *arrays = _carry_loop(run, x, tuple(arrays), layer_fn)
            seen[kind] += n
            expert_layers += n * (ff == "moe")
        counts = arrays[3].at[0, COUNTERS.index(f"{phase}s")].add(1)
        return x, (*arrays[:3], counts)

    # -- decode: one token of many sequences ---------------------------------

    def decode(self, params, x, arrays, positions, tables):
        """x: (S, d) embedded tokens at ``positions``; tables: (S, 1 + T).
        Returns (hidden (S, d), arrays)."""
        cfg = self.cfg
        slots, btab = tables[:, 0], tables[:, 1:]
        n_blocks, bs, n_slots = arrays[0].shape[1], arrays[0].shape[3], arrays[2].shape[1]
        phys = jnp.take_along_axis(btab, (positions // bs)[:, None], axis=1)[:, 0]
        write = _slots_write(phys, positions % bs, bs)

        def conv(h, layer, k_pool, v_pool, tails, l):
            with jax.named_scope("short_conv"):
                s, gate = self._conv_in(h, layer)
                with jax.named_scope("conv_update"):
                    tails, c = short_conv_decode(
                        tails, s, layer["conv"]["kernel"], l * n_slots + slots)
                return self._conv_out(h, layer, gate, c), k_pool, v_pool, tails

        def attention(h, layer, k_pool, v_pool, tails, l):
            base = l * n_blocks
            q, k, v = self._qkv(h, layer, positions)
            k_pool = write(k_pool, self._packed(k), base)
            v_pool = write(v_pool, self._packed(v), base)
            with jax.named_scope("gqa_attention"):
                att = gqa_paged_attention(
                    q, k_pool, v_pool, btab + base, positions, impl=cfg.attn_impl)
            return self._attn_out(h, layer, att), k_pool, v_pool, tails

        return self._layers(
            params, x, arrays, {"conv": conv, "full_attention": attention}, slots > 0, "decode")

    # -- prefill: a chunk of one sequence -------------------------------------

    def chunk(self, params, x, arrays, start, n_valid, table):
        """x: (C, d) embedded tokens of ONE sequence at ``start ..``, the
        first ``n_valid`` real; table: (1 + T,).  Returns (the last valid
        token's hidden (1, d), arrays)."""
        slot, btab = table[0], table[1:]
        C = x.shape[0]
        n_blocks, bs, n_slots = arrays[0].shape[1], arrays[0].shape[3], arrays[2].shape[1]
        positions = start + jnp.arange(C, dtype=jnp.int32)
        write = _chunk_write(btab, start, n_valid, C, bs)

        def conv(h, layer, k_pool, v_pool, tails, l):
            with jax.named_scope("short_conv"):
                s, gate = self._conv_in(h, layer)
                with jax.named_scope("conv_update"):
                    tails, c = short_conv_chunk(
                        tails, s, layer["conv"]["kernel"], l * n_slots + slot, start == 0,
                        n_valid)
                return self._conv_out(h, layer, gate, c), k_pool, v_pool, tails

        def attention(h, layer, k_pool, v_pool, tails, l):
            base = l * n_blocks
            q, k, v = self._qkv(h, layer, positions)
            k_pool = write(k_pool, self._packed(k), base)
            v_pool = write(v_pool, self._packed(v), base)
            with jax.named_scope("chunk_attention"):
                att = gqa_chunk_attention(
                    q.astype(self.dt), k_pool, v_pool, btab + base, positions, start + n_valid)
            return self._attn_out(h, layer, att), k_pool, v_pool, tails

        x, arrays = self._layers(
            params, x, arrays, {"conv": conv, "full_attention": attention},
            jnp.arange(C) < n_valid, "chunk")
        return jax.lax.dynamic_slice_in_dim(x, jnp.maximum(n_valid - 1, 0), 1), arrays
