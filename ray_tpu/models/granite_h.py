"""Granite-4.0-H (ibm-granite/granite-4.0-h-small, ``model_type``
``granitemoehybrid``) as ONE chip of an expert-parallel deployment serves it:
layers of TWO kinds, a Mamba-2 mixer in most and attention without any
positional encoding in one of every ten, and an expert layer closing EVERY
layer.

``h`` is the float32 residual stream, ``RMSNorm`` has a learned scale::

    h   = E[token] * embedding_multiplier
    h  += residual_multiplier * Mixer(RMSNorm_1(h))
    y   = RMSNorm_2(h)
    h  += residual_multiplier * (Routed(y) + Shared(y))
    logits = RMSNorm_f(h) E^T / logits_scaling          (the head is tied)

* **Mixer, a ``mamba`` layer** (Mamba-2, ``ops.ssd``): ``[z d_ssm | xBC d_ssm +
  2 G N | dt H] = u W_in``, no bias; ``xBC`` through a causal depthwise
  convolution of width ``d_conv`` with bias, then SiLU, split ``[x | B |
  C]``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, one number a
  head; the recurrence of ``ops.ssd``; ``RMSNorm(y . silu(z))`` over each of
  the ``G`` groups (gate, THEN norm; ONE group at the published sizes: all of
  ``d_ssm``), learned scale; then ``W_out``.
* **Mixer, an ``attention`` layer**: ``q``, ``k``, ``v`` without bias and
  WITHOUT rotary (``position_embedding_type`` ``nope``); causal softmax of
  ``q . k * attention_multiplier`` in float32, ``H / K`` query heads a
  key-value head (``ops.gqa_attention``, whose scale is ``e ** -0.5``: the
  rest, ``attention_multiplier * sqrt(e)``, goes into ``q`` before it is
  rounded); then ``W_o``.
* **Routed**: ``z = y W_r`` over ALL ``n_routed_experts`` in float32, the
  ``experts_per_tok`` largest LOGITS chosen, ``w = softmax(z[chosen])``
  (``ops.moe.route_logits``: no sigmoid, no bias, no scaling factor); ``sum
  over chosen AND held e of w_e Expert_e(y)``, ``Expert_e(y) = (silu(y
  W_gate,e) . (y W_up,e)) W_down,e`` (the published ``W_in,e``'s two halves
  as two matrices).  THIS CHIP holds experts ``expert_offset .. expert_offset
  + experts_held`` (one of ``expert_parallel`` chips that share each layer)
  and adds their part alone, droplessly; the absent experts' part is the
  other chips' and is left out, here and in the plain reference.  **Shared**:
  the same gated form at width ``d_shared``, a whole copy a chip.

What a sequence holds on the device (``llm.cache.HybridPool``) is SPLIT BY
LAYER KIND: a slot of SSD state ``(H, P, N)`` float32 and of the
convolution's last ``d_conv - 1`` inputs in each Mamba layer, blocks of K and
V in each attention layer, nothing else.  So ``kv_layout()["n_layers"]``
counts the attention layers and ``state_leaves()`` the Mamba layers.  The
layer loop is ``blocks.pattern_layers``: one ``_carry_loop`` for each RUN of
layers of one kind (``runs()``: at the published pattern five Mamba layers,
one attention layer, four Mamba layers), every run over the same pools; a
layer's held experts are indexed out of ONE flat array of every layer's
(``ops.moe.expert_layer``).  What the family shares with others it takes from
``models.blocks`` (the Mamba-2 mixer and its two steps, the paged K/V step,
the pattern loop) and ``ops.moe`` (the routed layer's ledger: ``counters`` is
what the programs count on the device, ``stats()["moe"]``); its projections,
its multipliers and its pattern are HERE.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.models.blocks import (
    Mamba2, check_share, dot32, gated_mlp_init, last_valid, normal_layers, paged_kv_chunk,
    paged_kv_decode, pattern_layers, pattern_of, rmsnorm, runs_of)
from ray_tpu.ops.moe import (
    count_routed, counters_shape, expert_layer, held_pairs, read_counters, route_logits, swiglu)

#: the published pattern's first period
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHConfig:
    #: the slice of the published 100,352 rows held here (the tied embedding)
    vocab_size: int = 50176
    seq_len: int = 131072
    d_model: int = 4096
    #: the published 40 cut to one period of 10 (pipeline stages hold the
    #: rest); ``layer_types`` names each layer's mixer
    n_layers: int = 10
    layer_types: tuple = PERIOD
    #: every layer closes with the expert layer (the readers' key)
    n_dense_layers: int = 0
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    #: the Mamba-2 mixer: inner width (heads x head size), heads, state
    #: columns, groups of B and C, convolution width, tokens a sub-chunk
    d_ssm: int = 8192
    ssm_heads: int = 128
    d_state: int = 128
    n_groups: int = 1
    d_conv: int = 4
    ssm_chunk: int = 256
    d_expert: int = 768
    d_shared: int = 1536
    n_shared_experts: int = 1
    #: the router's width, as published; of them this chip holds
    #: ``experts_held`` from ``expert_offset``, one of ``expert_parallel``
    #: chips that share each layer
    n_routed_experts: int = 72
    experts_held: int = 36
    expert_offset: int = 0
    expert_parallel: int = 2
    experts_per_tok: int = 10
    rms_norm_eps: float = 1e-5
    #: the muP multipliers, as published
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    #: the initializer's spread of the embedding, of the attention scores
    #: ``q . k * attention_multiplier`` (through ``W_q``), what ``W_o`` is
    #: widened by, and its ranges of A and of the step size (through dt_bias)
    init_range: float = 0.005
    score_spread: float = 3.0
    attn_out_gain: float = 1.0
    a_min: float = 1.0
    a_max: float = 16.0
    dt_min: float = 1e-3
    dt_max: float = 0.1
    dtype: str = "bfloat16"
    #: the SSD state's dtype.  float32: a bfloat16 state loses the small
    #: steps (dt down to 1e-3) of a state it has integrated
    state_dtype: str = "float32"
    attn_impl: str = "auto"

    #: what a sequence holds on the device (``llm.engine``): blocks of the
    #: attention layers' K/V AND a slot of the Mamba layers' state
    cache_kind = "hybrid"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", pattern_of(
            self.layer_types, self.n_layers, ("mamba", "attention")))
        if self.n_heads % self.n_kv_heads or self.ssm_heads % self.n_groups:
            raise ValueError("query heads and SSM heads come in whole groups")
        if self.d_ssm % self.ssm_heads:
            raise ValueError("d_ssm must be whole heads")
        check_share(
            self.n_routed_experts, self.expert_offset, self.experts_held, self.experts_per_tok)

    @property
    def ssm_head_dim(self) -> int:
        return self.d_ssm // self.ssm_heads

    @property
    def conv_dim(self) -> int:
        """The channels that go through the convolution: ``[x | B | C]``."""
        return self.d_ssm + 2 * self.n_groups * self.d_state

    def n_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def runs(self) -> tuple:
        """The layers as runs of one kind: ``((kind, how many), ...)``."""
        return runs_of(self.layer_types)

    def mixer(self) -> Mamba2:
        """The Mamba layers' mixer (``models.blocks``); no muP vector."""
        return Mamba2(
            d_ssm=self.d_ssm, heads=self.ssm_heads, d_state=self.d_state, n_groups=self.n_groups,
            d_conv=self.d_conv, eps=self.rms_norm_eps, dtype=jnp.dtype(self.dtype),
            sub=self.ssm_chunk, impl=self.attn_impl)

    def serving_body(self) -> "GraniteHBody":
        return GraniteHBody(self)


def granite_h_init(rng: jax.Array, cfg: GraniteHConfig) -> dict:
    """Seeded random parameters, made IN ``cfg.dtype`` a layer (an expert)
    at a time (float32 masters of 4.76B parameters would be 19 GB).
    ``params["runs"][i]`` holds run ``i``'s layers stacked (its mixers, both
    norms, router and shared MLP), ``params["experts"]`` EVERY layer's held
    experts flat, layer ``l``'s from ``l * experts_held``.

    The multipliers are muP's and trained weights carry their inverse: a
    MIXER's projection onto the stream (``W_out``, ``W_o``) at ``fan_in **
    -0.5 / residual_multiplier`` (a layer's mixer adds about a unit), every
    other at ``fan_in ** -0.5``: the experts' and the shared MLP's ``W_down``
    (with the inverse on them too a flipped routing choice, which bfloat16
    products upstream of the router make in two rows of three, moves the
    logits as far as the SSD states held at 3 bits of mantissa do: the
    configuration's ``correctness`` has the readings) and the router's (on a
    normed input its logits are about N(0, 1): near-uniform routing).  The
    embedding normal at ``init_range``, SMALL: the head is
    tied, so the part of the last stream that is still the token's own row
    scores ``sqrt(d)`` times its share against that same row; at ``1 /
    embedding_multiplier`` (a unit stream beside ten layers' units) that is
    17 spreads of the logits and every position's largest logit is its own
    input token, whatever the layers computed; at 0.005 it is one spread.
    ``W_k`` at ``head_dim ** 0.25`` times that and ``W_q`` at
    ``score_spread`` times more: at ``fan_in ** -0.5`` a score times
    ``attention_multiplier`` has a spread of 0.09, the softmax returns the
    values' mean and nothing the K/V cache holds reaches the logits; at a
    spread of 3 a query's weight lies on a few keys.  ``W_o`` times
    ``attn_out_gain`` beside.  ``A`` uniform in ``[a_min, a_max]``, the step
    size log-uniform in ``[dt_min, dt_max]`` through ``dt_bias`` (its inverse
    softplus), ``D`` 1, the convolution AND its bias uniform at ``d_conv **
    -0.5``, norm scales 1."""
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    onto = 1.0 / cfg.residual_multiplier

    normal = functools.partial(normal_layers, dtype=dt)
    mlp = functools.partial(gated_mlp_init, d=d, make=normal)

    def closing(key, n: int) -> dict:
        ks = jax.random.split(key, 2)
        return {"ln1": {"scale": jnp.ones((n, d), dt)}, "ln2": {"scale": jnp.ones((n, d), dt)},
                "router": {"kernel": normal(ks[0], n, (d, cfg.n_routed_experts), d**-0.5)},
                "shared": mlp(ks[1], n, width=cfg.d_shared)}

    def mamba(key, n: int) -> dict:
        ks = jax.random.split(key, 7)
        return dict(closing(ks[1], n), **cfg.mixer().init(
            (ks[0], *ks[2:]), n, d, d**-0.5, cfg.d_ssm**-0.5 * onto,
            (cfg.a_min, cfg.a_max), (cfg.dt_min, cfg.dt_max)))

    def attention(key, n: int) -> dict:
        ks = jax.random.split(key, 5)
        wide = d**-0.5 * cfg.head_dim**0.25
        return dict(
            closing(ks[0], n),
            q={"kernel": normal(ks[1], n, (d, hq), cfg.score_spread * wide)},
            k={"kernel": normal(ks[2], n, (d, hkv), wide)},
            v={"kernel": normal(ks[3], n, (d, hkv), d**-0.5)},
            o={"kernel": normal(ks[4], n, (hq, d), hq**-0.5 * onto * cfg.attn_out_gain)},
        )

    runs = cfg.runs()
    ks = jax.random.split(rng, len(runs) + 2)
    made = {"mamba": mamba, "attention": attention}
    return {
        "embed": {"tokens": normal(
            ks[0], 1, (cfg.vocab_size, d), cfg.init_range)[0]},
        "runs": [made[kind](k, n) for k, (kind, n) in zip(ks[2:], runs)],
        "experts": mlp(ks[1], cfg.n_layers * cfg.experts_held, width=cfg.d_expert),
        "ln_f": {"scale": jnp.ones((d,), dt)},
    }


class GraniteHBody:
    """The family's traced layer programs for ``HybridModelRunner``.
    ``arrays`` is ``(k, v, conv, ssd, counters)``: K and V ``(attention
    layers, blocks, K, block, e)``, the convolution's tails ``(Mamba layers,
    slots + 1, (d_conv - 1) * conv_dim)``, a slot's ONE row
    (``blocks.Mamba2``), the SSD states ``(Mamba layers, slots
    + 1, H, P, N)`` and the device's own counts (``ops.moe.counters_shape``).
    A table row is ``[slot, block table...]``, slot 0 and block 0 the trash a
    dead decode row and a padded chunk row write; a dead row has no pair in
    the expert layer and counts nowhere."""

    def __init__(self, cfg: GraniteHConfig):
        self.cfg = cfg
        self.dt = jnp.dtype(cfg.dtype)
        self.ssm = cfg.mixer()
        #: ``ops.gqa_attention`` scales by ``e ** -0.5``; the rest goes into q
        self.q_scale = cfg.attention_multiplier * math.sqrt(cfg.head_dim)

    # -- what the pools hold ----------------------------------------------

    def kv_layout(self) -> dict:
        """The paged pool: the ATTENTION layers' K and V, a key-value head a
        head."""
        cfg = self.cfg
        return {"n_layers": cfg.n_of("attention"), "n_heads": cfg.n_kv_heads,
                "head_dim": cfg.head_dim, "dtype": cfg.dtype}

    def state_leaves(self, block_size: int) -> dict:
        """name -> (layers, one slot's shape, dtype): the MAMBA layers'."""
        return self.ssm.state_leaves(self.cfg.n_of("mamba"), self.cfg.state_dtype)

    def counters(self) -> tuple:
        return counters_shape(self.cfg.experts_held)

    read_counters = staticmethod(read_counters)

    # -- shared layer math --------------------------------------------------

    def embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["embed"]["tokens"][tokens].astype(
                jnp.float32) * self.cfg.embedding_multiplier

    def lm_head(self, params, h):
        """The tied head: the embedding's rows against the normed stream."""
        with jax.named_scope("lm_head"):
            y = rmsnorm(h, params["ln_f"]["scale"], self.cfg.rms_norm_eps).astype(self.dt)
            return jnp.einsum("nd,vd->nv", y, params["embed"]["tokens"].astype(self.dt),
                              preferred_element_type=jnp.float32) / self.cfg.logits_scaling

    def _norm(self, h, layer, which: str):
        return rmsnorm(h, layer[which]["scale"], self.cfg.rms_norm_eps)

    def _qkv(self, u, layer):
        """q (n, H, e), k, v (n, K, e) in the compute dtype; NO rotary."""
        cfg, n = self.cfg, u.shape[0]
        with jax.named_scope("qkv"):
            a = u.astype(self.dt)
            q = (dot32(a, layer["q"]["kernel"]) * self.q_scale).reshape(
                n, cfg.n_heads, cfg.head_dim)
            k = dot32(a, layer["k"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            v = dot32(a, layer["v"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            return q.astype(self.dt), k.astype(self.dt), v.astype(self.dt)

    def _attn_out(self, h, layer, att):
        with jax.named_scope("attn_out"):
            return h + self.cfg.residual_multiplier * dot32(
                att.astype(self.dt).reshape(h.shape[0], -1), layer["o"]["kernel"])

    def _expert_mlp(self, h, layer, live, counts, phase: str, experts, index):
        """The expert layer's part this chip holds, and the shared MLP.
        ``counts`` (``ops.moe``'s ledger) gets this layer through
        ``count_routed``.  ``experts``: the held experts of every layer, flat,
        this layer's from ``index * experts_held``."""
        cfg = self.cfg
        with jax.named_scope("moe_router"):
            y32 = self._norm(h, layer, "ln2")
            chosen, weights = route_logits(y32, layer["router"]["kernel"], cfg.experts_per_tok)
            mask, wmat = held_pairs(chosen, weights, cfg.expert_offset, cfg.experts_held, live)
            counts = count_routed(counts, mask, phase)
        y, sh = y32.astype(self.dt), layer["shared"]
        with jax.named_scope("moe_experts"):
            routed = expert_layer(y, mask, wmat, experts["gate"], experts["up"],
                                  experts["down"], first=index * cfg.experts_held,
                                  top_k=cfg.experts_per_tok, impl=cfg.attn_impl)
        with jax.named_scope("moe_shared"):
            return h + cfg.residual_multiplier * (
                routed + swiglu(y, sh["gate"], sh["up"], sh["down"])), counts

    def _layers(self, params, x, arrays, live, phase: str, slots, ssm, attend):
        """``blocks.pattern_layers`` over the runs, the expert layer closing
        EVERY layer: a step's layers, written once for both steps.  ``ssm(u,
        layer, conv, ssd, at)`` is the step's Mamba-2 step (``self.ssm.decode``
        / ``.chunk``), ``attend`` its paged K/V step, ``slots`` its slot(s) of
        state, ``live`` its rows that count."""
        cfg, experts = self.cfg, params["experts"]
        n_blocks, n_slots = arrays[0].shape[1], arrays[2].shape[1]

        def mamba(h, layer, k_pool, v_pool, conv, ssd, l):
            at = l * n_slots + slots
            with jax.named_scope("ssm"):
                y, conv, ssd = ssm(self._norm(h, layer, "ln1"), layer, conv, ssd, at)
                h = h + cfg.residual_multiplier * y
            return h, k_pool, v_pool, conv, ssd

        def attention(h, layer, k_pool, v_pool, conv, ssd, l):
            base = l * n_blocks
            q, k, v = self._qkv(self._norm(h, layer, "ln1"), layer)
            att, k_pool, v_pool = attend(q, k, v, k_pool, v_pool, base)
            return self._attn_out(h, layer, att), k_pool, v_pool, conv, ssd

        closing = lambda h, layer, counts, m: self._expert_mlp(  # noqa: E731
            h, layer, live, counts, phase, experts, m)
        return pattern_layers(
            [(kind, "moe", n) for kind, n in cfg.runs()], params["runs"], x, arrays,
            {"mamba": mamba, "attention": attention}, {"moe": closing}, phase)

    def decode(self, params, x, arrays, positions, tables):
        """One token of many sequences.  x: (S, d) embedded tokens at
        ``positions``; tables: (S, 1 + T).  Returns (hidden (S, d), arrays)."""
        slots, btab = tables[:, 0], tables[:, 1:]
        live = slots > 0
        attend = paged_kv_decode(arrays[0], btab, positions, self.cfg.attn_impl)
        ssm = functools.partial(self.ssm.decode, live=live)
        return self._layers(params, x, arrays, live, "decode", slots, ssm, attend)

    def chunk(self, params, x, arrays, start, n_valid, table):
        """A prefill chunk.  x: (C, d) embedded tokens of ONE sequence at
        ``start ..``, the first ``n_valid`` real; table: (1 + T,).  Returns
        (the last valid token's hidden (1, d), arrays)."""
        slot, btab = table[0], table[1:]
        positions = start + jnp.arange(x.shape[0], dtype=jnp.int32)
        valid, fresh = jnp.arange(x.shape[0]) < n_valid, start == 0
        attend = paged_kv_chunk(arrays[0], btab, positions, start, n_valid)
        ssm = functools.partial(self.ssm.chunk, fresh=fresh, n_valid=n_valid, valid=valid)
        x, arrays = self._layers(params, x, arrays, valid, "chunk", slot, ssm, attend)
        return last_valid(x, n_valid), arrays
