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
layer loop is one ``_carry_loop`` for each RUN of layers of one kind
(``runs()``: at the published pattern five Mamba layers, one attention layer,
four Mamba layers), every run over the same pools; a layer's held experts
are indexed out of ONE flat array of every layer's (``ops.moe.expert_layer``).
``counters`` is what the programs count on the device, under Kimi-K2.5's
names and one more (``decode_tile_rows``: the rows the expert layer computed
in decodes, in either of its forms).
"""

from __future__ import annotations

import dataclasses
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.model_runner import _carry_loop, _chunk_write, _slots_write
from ray_tpu.ops.gqa_attention import gqa_chunk_attention, gqa_paged_attention
from ray_tpu.ops.moe import (
    batch_steps, expert_layer, held_pairs, route_logits, swiglu, tile_rows)
from ray_tpu.ops.ssd import ssd_chunk, ssd_decode

#: ``stats()["moe"]``: the scalar counters, then ``load`` (one a held expert)
COUNTERS = ("decode_pairs", "decode_touched", "decodes", "chunk_pairs", "chunks",
            "decode_tile_rows", "decode_expert_steps")
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
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.n_layers or set(self.layer_types) != {
                "mamba", "attention"}:
            raise ValueError("layer_types names n_layers mixers, 'mamba' and 'attention' both")
        if self.n_heads % self.n_kv_heads or self.ssm_heads % self.n_groups:
            raise ValueError("query heads and SSM heads come in whole groups")
        if self.d_ssm % self.ssm_heads:
            raise ValueError("d_ssm must be whole heads")
        if self.expert_offset + self.experts_held > self.n_routed_experts:
            raise ValueError("the held experts lie outside the router's width")
        if self.experts_per_tok > self.n_routed_experts:
            raise ValueError("more experts a token than the router has")

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
        return tuple((kind, len(list(g))) for kind, g in itertools.groupby(self.layer_types))

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

    def normal(key, n: int, shape: tuple, std: float):
        """(n,) + shape, one layer at a time."""
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape, jnp.float32) * std).astype(dt),
            jax.random.split(key, n))

    def mlp(key, n: int, width: int) -> dict:
        ks = jax.random.split(key, 3)
        return {"gate": normal(ks[0], n, (d, width), d**-0.5),
                "up": normal(ks[1], n, (d, width), d**-0.5),
                "down": normal(ks[2], n, (width, d), width**-0.5)}

    def closing(key, n: int) -> dict:
        ks = jax.random.split(key, 2)
        return {"ln1": {"scale": jnp.ones((n, d), dt)}, "ln2": {"scale": jnp.ones((n, d), dt)},
                "router": {"kernel": normal(ks[0], n, (d, cfg.n_routed_experts), d**-0.5)},
                "shared": mlp(ks[1], n, cfg.d_shared)}

    def mamba(key, n: int) -> dict:
        ks = jax.random.split(key, 7)
        step = jnp.exp(jax.random.uniform(ks[0], (n, cfg.ssm_heads)) * (
            math.log(cfg.dt_max) - math.log(cfg.dt_min)) + math.log(cfg.dt_min))
        width = cfg.d_ssm + cfg.conv_dim + cfg.ssm_heads
        taps = lambda k, shape: (jax.random.uniform(  # noqa: E731
            k, shape, jnp.float32, -1.0, 1.0) * cfg.d_conv**-0.5).astype(dt)
        return dict(
            closing(ks[1], n),
            ssm_in={"kernel": normal(ks[2], n, (d, width), d**-0.5)},
            conv={"kernel": taps(ks[3], (n, cfg.d_conv, cfg.conv_dim)),
                  "bias": taps(ks[4], (n, cfg.conv_dim))},
            dt_bias=step + jnp.log(-jnp.expm1(-step)),
            A_log=jnp.log(jax.random.uniform(
                ks[5], (n, cfg.ssm_heads), jnp.float32, cfg.a_min, cfg.a_max)),
            D=jnp.ones((n, cfg.ssm_heads), jnp.float32),
            ssm_norm={"scale": jnp.ones((n, cfg.d_ssm), dt)},
            ssm_out={"kernel": normal(ks[6], n, (cfg.d_ssm, d), cfg.d_ssm**-0.5 * onto)},
        )

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
        "experts": mlp(ks[1], cfg.n_layers * cfg.experts_held, cfg.d_expert),
        "ln_f": {"scale": jnp.ones((d,), dt)},
    }


def _rmsnorm(x, scale, eps):
    """RMSNorm in float32 (x: the float32 stream, or a float32 product)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _dot32(x, kernel):
    """x @ kernel on x's dtype, float32 out."""
    return jnp.dot(x, kernel.astype(x.dtype), preferred_element_type=jnp.float32)


class GraniteHBody:
    """The family's traced layer programs for ``HybridModelRunner``.
    ``arrays`` is ``(k, v, conv, ssd, counters)``: K and V ``(attention
    layers, blocks, K, block, e)``, the convolution's tails ``(Mamba layers,
    slots + 1, d_conv - 1, conv_dim)``, the SSD states ``(Mamba layers, slots
    + 1, H, P, N)`` and the device's own counts ``(1, len(COUNTERS) +
    experts_held)`` int32.  A table row is ``[slot, block table...]``, slot 0 and block 0 the
    trash a dead decode row and a padded chunk row write; a dead row has no
    pair in the expert layer and counts nowhere."""

    def __init__(self, cfg: GraniteHConfig):
        self.cfg = cfg
        self.dt = jnp.dtype(cfg.dtype)
        #: where z ends and ``[x | B | C]`` ends in ``W_in``'s columns, and
        #: where x and B end within ``[x | B | C]``
        self.z_end, self.conv_end = cfg.d_ssm, cfg.d_ssm + cfg.conv_dim
        self.x_end, self.b_end = cfg.d_ssm, cfg.d_ssm + cfg.n_groups * cfg.d_state
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
        cfg, n = self.cfg, self.cfg.n_of("mamba")
        return {
            "conv": (n, (cfg.d_conv - 1, cfg.conv_dim), cfg.dtype),
            "ssd": (n, (cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state), cfg.state_dtype),
        }

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
            return params["embed"]["tokens"][tokens].astype(
                jnp.float32) * self.cfg.embedding_multiplier

    def lm_head(self, params, h):
        """The tied head: the embedding's rows against the normed stream."""
        with jax.named_scope("lm_head"):
            y = _rmsnorm(h, params["ln_f"]["scale"], self.cfg.rms_norm_eps).astype(self.dt)
            return jnp.einsum("nd,vd->nv", y, params["embed"]["tokens"].astype(self.dt),
                              preferred_element_type=jnp.float32) / self.cfg.logits_scaling

    def _norm(self, h, layer, which: str):
        return _rmsnorm(h, layer[which]["scale"], self.cfg.rms_norm_eps)

    def _ssm_in(self, u, layer):
        """The input projection: (z (n, d_ssm) float32, ``[x | B | C]``
        before the convolution in the compute dtype, the step size (n, H)
        float32 after its softplus)."""
        p = _dot32(u.astype(self.dt), layer["ssm_in"]["kernel"])
        step = jax.nn.softplus(p[:, self.conv_end:] + layer["dt_bias"].astype(jnp.float32))
        return p[:, :self.z_end], p[:, self.z_end:self.conv_end].astype(self.dt), step

    def _conv(self, window, layer):
        """``window``: (..., d_conv + n - 1, conv_dim) inputs, the oldest
        first -> SiLU of the causal depthwise convolution at the last ``n``,
        float32, split into x (n, H, P), B and C (n, G, N)."""
        cfg = self.cfg
        n = window.shape[-2] - cfg.d_conv + 1
        w32, kern = window.astype(jnp.float32), layer["conv"]["kernel"].astype(jnp.float32)
        out = sum(w32[..., i:i + n, :] * kern[i] for i in range(cfg.d_conv))
        out = jax.nn.silu(out + layer["conv"]["bias"].astype(jnp.float32))
        out = out.reshape(-1, cfg.conv_dim)
        rows = out.shape[0]
        return (out[:, :self.x_end].reshape(rows, cfg.ssm_heads, cfg.ssm_head_dim),
                out[:, self.x_end:self.b_end].reshape(rows, cfg.n_groups, cfg.d_state),
                out[:, self.b_end:].reshape(rows, cfg.n_groups, cfg.d_state))

    def _ssm_out(self, y, z, layer):
        """Gate, THEN the norm within each group, then the output projection."""
        cfg = self.cfg
        gated = (y.reshape(z.shape) * jax.nn.silu(z)).reshape(z.shape[0], cfg.n_groups, -1)
        normed = gated * jax.lax.rsqrt(
            (gated * gated).mean(-1, keepdims=True) + cfg.rms_norm_eps)
        normed = normed.reshape(z.shape) * layer["ssm_norm"]["scale"].astype(jnp.float32)
        return _dot32(normed.astype(self.dt), layer["ssm_out"]["kernel"])

    def _qkv(self, u, layer):
        """q (n, H, e), k, v (n, K, e) in the compute dtype; NO rotary."""
        cfg, n = self.cfg, u.shape[0]
        with jax.named_scope("qkv"):
            a = u.astype(self.dt)
            q = (_dot32(a, layer["q"]["kernel"]) * self.q_scale).reshape(
                n, cfg.n_heads, cfg.head_dim)
            k = _dot32(a, layer["k"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            v = _dot32(a, layer["v"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            return q.astype(self.dt), k.astype(self.dt), v.astype(self.dt)

    def _attn_out(self, h, layer, att):
        with jax.named_scope("attn_out"):
            return h + self.cfg.residual_multiplier * _dot32(
                att.astype(self.dt).reshape(h.shape[0], -1), layer["o"]["kernel"])

    @staticmethod
    def _a(layer):
        return -jnp.exp(layer["A_log"].astype(jnp.float32))

    def _expert_mlp(self, h, layer, live, counts, phase: str, experts, index):
        """The expert layer's part this chip holds, and the shared MLP.
        ``counts`` gets this layer's pairs under ``<phase>_pairs``, its load
        by held expert and, in a decode, its touched experts, the rows the
        expert layer computed and the steps its batch form made.  ``experts``: the held experts of every layer,
        flat, this layer's from ``index * experts_held``."""
        cfg = self.cfg
        with jax.named_scope("moe_router"):
            y32 = self._norm(h, layer, "ln2")
            chosen, weights = route_logits(y32, layer["router"]["kernel"], cfg.experts_per_tok)
            mask, wmat = held_pairs(chosen, weights, cfg.expert_offset, cfg.experts_held, live)
            load = mask.sum(axis=0).astype(jnp.int32)
            counts = counts.at[COUNTERS.index(f"{phase}_pairs")].add(load.sum())
            counts = counts.at[len(COUNTERS):].add(load)
            if phase == "decode":
                counts = counts.at[COUNTERS.index("decode_touched")].add(
                    (load > 0).sum().astype(jnp.int32))
                counts = counts.at[COUNTERS.index("decode_tile_rows")].add(
                    tile_rows(load, mask.shape[0]))
                counts = counts.at[COUNTERS.index("decode_expert_steps")].add(
                    batch_steps(load, mask.shape[0]))
        y, sh = y32.astype(self.dt), layer["shared"]
        with jax.named_scope("moe_experts"):
            routed = expert_layer(y, mask, wmat, experts["gate"], experts["up"],
                                  experts["down"], first=index * cfg.experts_held,
                                  top_k=cfg.experts_per_tok, impl=cfg.attn_impl)
        with jax.named_scope("moe_shared"):
            return h + cfg.residual_multiplier * (
                routed + swiglu(y, sh["gate"], sh["up"], sh["down"])), counts

    def _layers(self, params, x, arrays, mixers: dict, live, phase: str):
        """One ``_carry_loop`` a run of layers of one kind, each over ALL the
        pools (a run leaves the other kind's as they came).  ``mixers[kind](h,
        layer, k, v, conv, ssd, l)`` is the step's mixer of the ``l``-th layer
        of that kind and gives ``(h, k, v, conv, ssd)``."""
        n_blocks, experts = arrays[0].shape[1], params["experts"]
        seen, done = {"mamba": 0, "attention": 0}, 0
        for (kind, n), run in zip(self.cfg.runs(), params["runs"]):

            def layer_fn(h, layer, k, v, conv, ssd, counts, base,
                         mix=mixers[kind], first=seen[kind], index=done):
                at = base // n_blocks  # the layer's place in its run
                h, k, v, conv, ssd = mix(h, layer, k, v, conv, ssd, first + at)
                h, counts = self._expert_mlp(h, layer, live, counts, phase, experts, index + at)
                return h, k, v, conv, ssd, counts

            x, *arrays = _carry_loop(run, x, tuple(arrays), layer_fn)
            seen[kind] += n
            done += n
        counts = arrays[4].at[0, COUNTERS.index(f"{phase}s")].add(1)
        return x, (*arrays[:4], counts)

    # -- decode: one token of many sequences ---------------------------------

    def decode(self, params, x, arrays, positions, tables):
        """x: (S, d) embedded tokens at ``positions``; tables: (S, 1 + T).
        Returns (hidden (S, d), arrays)."""
        cfg = self.cfg
        slots, btab = tables[:, 0], tables[:, 1:]
        n_blocks, bs, n_slots = arrays[0].shape[1], arrays[0].shape[3], arrays[2].shape[1]
        live = slots > 0
        phys = jnp.take_along_axis(btab, (positions // bs)[:, None], axis=1)[:, 0]
        write = _slots_write(phys, positions % bs, bs)

        def mamba(h, layer, k_pool, v_pool, conv, ssd, l):
            at = l * n_slots + slots
            with jax.named_scope("ssm"):
                z, raw, step = self._ssm_in(self._norm(h, layer, "ln1"), layer)
                window = jnp.concatenate([conv[at], raw[:, None, :]], axis=1)
                conv = conv.at[at].set(window[:, 1:])
                xs, b, c = self._conv(window, layer)
                with jax.named_scope("ssd_update"):
                    ssd, y = ssd_decode(ssd, xs, step, self._a(layer), b, c, layer["D"],
                                        at, live, impl=cfg.attn_impl)
                h = h + cfg.residual_multiplier * self._ssm_out(y, z, layer)
            return h, k_pool, v_pool, conv, ssd

        def attention(h, layer, k_pool, v_pool, conv, ssd, l):
            base = l * n_blocks
            q, k, v = self._qkv(self._norm(h, layer, "ln1"), layer)
            k_pool, v_pool = write(k_pool, k, base), write(v_pool, v, base)
            with jax.named_scope("gqa_attention"):
                att = gqa_paged_attention(q, k_pool, v_pool, btab + base, positions,
                                          impl=cfg.attn_impl)
            return self._attn_out(h, layer, att), k_pool, v_pool, conv, ssd

        return self._layers(
            params, x, arrays, {"mamba": mamba, "attention": attention}, live, "decode")

    # -- prefill: a chunk of one sequence -------------------------------------

    def chunk(self, params, x, arrays, start, n_valid, table):
        """x: (C, d) embedded tokens of ONE sequence at ``start ..``, the
        first ``n_valid`` real; table: (1 + T,).  Returns (the last valid
        token's hidden (1, d), arrays)."""
        cfg = self.cfg
        slot, btab = table[0], table[1:]
        C, taps = x.shape[0], cfg.d_conv - 1
        n_blocks, bs, n_slots = arrays[0].shape[1], arrays[0].shape[3], arrays[2].shape[1]
        positions = start + jnp.arange(C, dtype=jnp.int32)
        valid, fresh = jnp.arange(C) < n_valid, start == 0
        write = _chunk_write(btab, start, n_valid, C, bs)

        def mamba(h, layer, k_pool, v_pool, conv, ssd, l):
            at = l * n_slots + slot
            with jax.named_scope("ssm"):
                z, raw, step = self._ssm_in(self._norm(h, layer, "ln1"), layer)
                # a sequence's first chunk overwrites what the slot's last
                # owner left; the last ``taps`` valid inputs are what the
                # next token needs
                tail = jnp.where(fresh, 0, jax.lax.dynamic_index_in_dim(conv, at, 0, False))
                seq = jnp.concatenate([tail, raw], axis=0)              # (taps + C, D)
                conv = jax.lax.dynamic_update_index_in_dim(
                    conv, jax.lax.dynamic_slice_in_dim(seq, n_valid, taps), at, 0)
                xs, b, c = self._conv(seq, layer)
                with jax.named_scope("ssd_chunk"):
                    s0 = jnp.where(fresh, 0.0, jax.lax.dynamic_index_in_dim(
                        ssd, at, 0, False).astype(jnp.float32))
                    y, s1 = ssd_chunk(s0, xs, step, self._a(layer), b, c, layer["D"], valid,
                                      sub=cfg.ssm_chunk)
                    ssd = jax.lax.dynamic_update_index_in_dim(ssd, s1.astype(ssd.dtype), at, 0)
                h = h + cfg.residual_multiplier * self._ssm_out(y, z, layer)
            return h, k_pool, v_pool, conv, ssd

        def attention(h, layer, k_pool, v_pool, conv, ssd, l):
            base = l * n_blocks
            q, k, v = self._qkv(self._norm(h, layer, "ln1"), layer)
            k_pool, v_pool = write(k_pool, k, base), write(v_pool, v, base)
            with jax.named_scope("chunk_attention"):
                att = gqa_chunk_attention(q, k_pool, v_pool, btab + base, positions,
                                          start + n_valid)
            return self._attn_out(h, layer, att), k_pool, v_pool, conv, ssd

        x, arrays = self._layers(
            params, x, arrays, {"mamba": mamba, "attention": attention}, valid, "chunk")
        return jax.lax.dynamic_slice_in_dim(x, jnp.maximum(n_valid - 1, 0), 1), arrays
