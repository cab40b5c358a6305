"""Nemotron-H (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type``
``nemotron_h``) as ONE chip of an expert-parallel deployment serves it:
layers that are each ONE part, a Mamba-2 mixer (``M``), an attention layer
without any positional encoding (``*``) or an expert layer of UNGATED experts
(``E``), in the order ``hybrid_override_pattern`` spells.

``h`` is the float32 residual stream, ``RMSNorm`` has a learned scale; one
norm and one residual add a layer, whatever its kind::

    h   = E[token]
    h  += Part_kind(RMSNorm(h))                  (each of the pattern's letters)
    logits = RMSNorm_f(h) W_head                 (the head is NOT tied)

* **``M``** (Mamba-2, ``ops.ssd``): ``[z d_ssm | xBC d_ssm + 2 G N | dt H] = u
  W_in``, no bias, ``d_ssm = H x P`` (``expand`` is not used); ``xBC`` through
  a causal depthwise convolution of ``d_conv`` taps with bias, then SiLU, split
  ``[x | B | C]`` (``H / G`` heads read each group's B and C); ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``, ``D``, one number a head; the
  recurrence of ``ops.ssd``; ``RMSNorm(y . silu(z))`` within each of the ``G``
  groups (gate, THEN norm), learned scale; then ``W_out``, no bias.
* **``*``**: ``q`` (``H`` heads of ``e``), ``k``, ``v`` (``K`` heads) without
  bias and WITHOUT rotary; causal softmax of ``q . k / sqrt(e)`` in float32,
  ``H / K`` query heads a key-value head (``ops.gqa_attention``); then ``W_o``.
* **``E``** (DeepSeek-V3's router): ``s = sigmoid(y W_r)`` over ALL
  ``n_routed_experts`` in float32, the ``experts_per_tok`` largest of ``s +
  select_bias`` chosen, their weights ``s`` over their sum (+ ``route_eps``)
  times ``routed_scaling`` (``ops.moe.route``); ``Routed(y) = sum over chosen
  AND held e of w_e Expert_e(y)``, ``Expert_e(y) = relu(y W_up,e)**2 W_down,e``:
  TWO matrices an expert, no gate (``ops.moe.expert_mlp``: the weights choose
  the form).  THIS CHIP holds experts ``expert_offset .. expert_offset +
  experts_held`` (one of ``expert_parallel`` chips that share each layer) and
  adds their part alone, droplessly; the absent experts' part is the other
  chips' and is left out, here and in the plain reference.  ``Shared(y)``:
  the same ungated form at width ``d_shared``, a whole copy a chip; the layer
  adds ``Routed(y) + Shared(y)``.

What a sequence holds on the device (``llm.cache.HybridPool``) is SPLIT BY
LAYER KIND: a slot of SSD state ``(H, P, N)`` float32 and of the
convolution's last ``d_conv - 1`` inputs in each ``M`` layer, blocks of K and
V in each ``*`` layer, nothing in an ``E`` layer.  The layer loop is
``blocks.pattern_layers`` over PAIRS: a mixer with the expert layer that
follows it, or alone (``runs()``: the published 52 letters are 29 pairs in 19
runs of three kinds, ``(M, E)``, ``(M, -)`` and ``(*, E)``); the norm and the
residual belong to each part.  What the family shares with others it takes
from ``models.blocks`` (the Mamba-2 mixer and its two steps, the paged K/V
step, the pattern loop) and ``ops.moe`` (the router, both expert kernels, the
routed layer's ledger); its projections and its pattern are HERE.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.blocks import (
    Mamba2, check_share, dot32, last_valid, normal_layers, paged_kv_chunk,
    paged_kv_decode, pattern_layers, rmsnorm, runs_of)
from ray_tpu.ops.moe import (
    count_routed, counters_shape, expert_layer, held_pairs, read_counters, relu2, route)

#: ``hybrid_override_pattern`` as published, and its letters' names
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


def pairs_of(layer_types) -> tuple:
    """The layers as (mixer, closing) pairs: an expert layer closes the mixer
    it follows (every one follows a mixer: ``NemotronHConfig`` refuses another
    pattern); a mixer no expert layer follows stands alone (closing None)."""
    pairs = []
    for kind in layer_types:
        if kind == "moe":
            pairs[-1] = (pairs[-1][0], "moe")
        else:
            pairs.append((kind, None))
    return tuple(pairs)


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    seq_len: int = 262144
    d_model: int = 2688
    #: all of the published depth; ``pattern`` spells each layer's ONE part and
    #: ``layer_types`` names them (``__post_init__``)
    n_layers: int = 52
    pattern: str = PATTERN
    layer_types: tuple = ()
    #: the layers that are no expert layer (the benchmark's readers' key: the
    #: expert layers are ``n_layers - n_dense_layers``), counted from the pattern
    n_dense_layers: int = -1
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    #: the Mamba-2 mixer: heads, head size (inner width their product), state
    #: columns, groups of B and C, convolution width, tokens a sub-chunk
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    d_state: int = 128
    n_groups: int = 8
    d_conv: int = 4
    ssm_chunk: int = 128
    d_expert: int = 1856
    d_shared: int = 3712
    #: the experts' matrices are STORED with their width rounded up to whole
    #: rows of this many lanes: zero columns of ``W_up``, zero rows of
    #: ``W_down``, exact (``relu(0) ** 2 = 0``).  An array whose last axis is
    #: 1,856 = 14.5 x 128 XLA copies WHOLE into a padded layout before a kernel
    #: reads it (3.8 GB a layer: ``tests/test_tpu_aot_kernels.py``)
    expert_lanes: int = 128
    #: the router's width, as published; of them this chip holds
    #: ``experts_held`` from ``expert_offset``, one of ``expert_parallel``
    #: chips that share each layer
    n_routed_experts: int = 128
    experts_held: int = 16
    expert_offset: int = 0
    expert_parallel: int = 8
    experts_per_tok: int = 6
    routed_scaling: float = 2.5
    route_eps: float = 1e-20
    norm_eps: float = 1e-5
    #: the initializer's spread of the embedding, of the attention scores
    #: (through ``W_q``), what ``W_o`` and the ROUTED experts' ``W_down`` are
    #: scaled by, and the ranges of A and of the step size (through dt_bias)
    init_range: float = 1.0
    score_spread: float = 4.0
    attn_out_gain: float = 4.0
    expert_out_gain: float = 0.1
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
        if len(self.pattern) != self.n_layers or set(self.pattern) != set(KINDS):
            raise ValueError("pattern spells n_layers parts, M, * and E all three")
        if self.pattern[0] == "E" or "EE" in self.pattern:
            raise ValueError("an expert layer follows a mixer (as every published one does)")
        object.__setattr__(self, "layer_types", tuple(KINDS[c] for c in self.pattern))
        object.__setattr__(self, "n_dense_layers", self.n_layers - self.n_of("moe"))
        if self.n_heads % self.n_kv_heads or self.ssm_heads % self.n_groups:
            raise ValueError("query heads and SSM heads come in whole groups")
        check_share(
            self.n_routed_experts, self.expert_offset, self.experts_held, self.experts_per_tok)

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    def stored(self, width: int) -> int:
        """An expert's ``width`` as its matrices are stored: whole lane rows."""
        return -(-width // self.expert_lanes) * self.expert_lanes

    def n_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def runs(self) -> tuple:
        """The layers as runs of one kind of pair: ``((mixer, closing, how
        many), ...)``, the closing None where a mixer stands alone."""
        return tuple((*pair, n) for pair, n in runs_of(pairs_of(self.layer_types)))

    def mixer(self) -> Mamba2:
        """The ``M`` layers' mixer (``models.blocks``); no muP vector."""
        return Mamba2(
            d_ssm=self.d_ssm, heads=self.ssm_heads, d_state=self.d_state, n_groups=self.n_groups,
            d_conv=self.d_conv, eps=self.norm_eps, dtype=jnp.dtype(self.dtype),
            sub=self.ssm_chunk, impl=self.attn_impl)

    def serving_body(self) -> "NemotronHBody":
        return NemotronHBody(self)


def nemotron_h_init(rng: jax.Array, cfg: NemotronHConfig) -> dict:
    """Seeded random parameters, made IN ``cfg.dtype`` a layer (an expert) at
    a time (float32 masters of 5.9B parameters would be 23 GB).
    ``params["runs"][i]`` holds run ``i``'s pairs stacked (the mixer and its
    norm ``ln1``; where the pair has an expert layer its norm ``ln2``, router
    and shared expert), ``params["experts"]`` EVERY expert layer's held
    experts flat, ``up`` and ``down`` alone (each stored ``cfg.stored(d_expert)``
    wide, zeros past the published width), the ``m``-th expert layer's from ``m
    * experts_held``.

    Every projection normal at ``fan_in ** -0.5`` (a part adds about a unit to
    the stream), the router's too (on a normed input its logits are about N(0,
    1): near-uniform routing, the selection bias zero), norm scales 1, but for
    what the configuration file's ``assumed`` explains: the embedding normal at
    ``init_range`` and the UNTIED head at ``d ** -0.5`` (logits of spread 1);
    ``W_q`` at ``score_spread`` times ``fan_in ** -0.5`` (``q . k / sqrt(e)``
    then has a spread of ``score_spread`` and a query's weight lies on a few
    keys: at ``fan_in ** -0.5`` the spread is 1, the softmax returns nearly
    the values' mean and little the K/V cache holds reaches the logits),
    ``W_o`` times ``attn_out_gain`` (six attention layers among 52); the experts' and the shared expert's
    ``W_down`` at ``(1.5 f) ** -0.5``: for a unit ``z``, ``relu(z) ** 2`` has
    a second moment of 1.5, so an expert's output is about a unit as a gated
    one's is; the ROUTED experts' ``W_down`` times ``expert_out_gain`` beside:
    this chip adds the HELD experts' part alone, so a flipped routing choice,
    which bfloat16 products upstream of the router make on most rows
    somewhere in 23 expert layers, takes a whole ``w_e Expert_e(y)`` out of
    the stream or puts one in (the expert it is swapped for is mostly another
    chip's), and at a gain of 1 that outweighs states or keys at 3 bits of
    mantissa (the configuration's ``correctness`` has the readings).
    ``A`` uniform in ``[a_min, a_max]``, the step size log-uniform in
    ``[dt_min, dt_max]`` through ``dt_bias`` (its inverse softplus), ``D`` 1,
    the convolution AND its bias uniform at ``d_conv ** -0.5``."""
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    normal = functools.partial(normal_layers, dtype=dt)

    def ungated(key, n: int, width: int, out_gain: float = 1.0) -> dict:
        """``n`` ungated MLPs ``d -> width -> d`` for ``ops.moe.relu2``, stored
        at ``cfg.stored(width)``: the columns past ``width`` are zero."""
        ks, real = jax.random.split(key, 2), jnp.arange(cfg.stored(width)) < width
        down = (1.5 * width)**-0.5 * out_gain
        return {"up": normal(ks[0], n, (d, real.size), d**-0.5 * real),
                "down": normal(ks[1], n, (real.size, d), down * real[:, None])}

    def closing(key, n: int, kind) -> dict:
        if kind is None:
            return {}
        ks = jax.random.split(key, 2)
        return {"ln2": {"scale": jnp.ones((n, d), dt)},
                "router": {"kernel": normal(ks[0], n, (d, cfg.n_routed_experts), d**-0.5),
                           "bias": jnp.zeros((n, cfg.n_routed_experts), jnp.float32)},
                "shared": ungated(ks[1], n, cfg.d_shared)}

    def mamba(key, n: int) -> dict:
        ks = jax.random.split(key, 6)
        return dict({"ln1": {"scale": jnp.ones((n, d), dt)}}, **cfg.mixer().init(
            ks, n, d, d**-0.5, cfg.d_ssm**-0.5, (cfg.a_min, cfg.a_max),
            (cfg.dt_min, cfg.dt_max)))

    def attention(key, n: int) -> dict:
        ks = jax.random.split(key, 4)
        return {
            "ln1": {"scale": jnp.ones((n, d), dt)},
            "q": {"kernel": normal(ks[0], n, (d, hq), cfg.score_spread * d**-0.5)},
            "k": {"kernel": normal(ks[1], n, (d, hkv), d**-0.5)},
            "v": {"kernel": normal(ks[2], n, (d, hkv), d**-0.5)},
            "o": {"kernel": normal(ks[3], n, (hq, d), hq**-0.5 * cfg.attn_out_gain)},
        }

    runs = cfg.runs()
    ks = jax.random.split(rng, 2 * len(runs) + 3)
    made = {"mamba": mamba, "attention": attention}
    return {
        "embed": {"tokens": normal(ks[0], 1, (cfg.vocab_size, d), cfg.init_range)[0]},
        "lm_head": {"kernel": normal(ks[1], 1, (d, cfg.vocab_size), d**-0.5)[0]},
        "runs": [dict(made[mixer](k_mix, n), **closing(k_close, n, close))
                 for k_mix, k_close, (mixer, close, n) in zip(ks[3::2], ks[4::2], runs)],
        "experts": ungated(ks[2], cfg.n_of("moe") * cfg.experts_held, cfg.d_expert,
                           cfg.expert_out_gain),
        "ln_f": {"scale": jnp.ones((d,), dt)},
    }


class NemotronHBody:
    """The family's traced layer programs for ``HybridModelRunner``.
    ``arrays`` is ``(k, v, conv, ssd, counters)``: K and V ``(attention
    layers, blocks, K, block, e)``, the convolution's tails ``(Mamba layers,
    slots + 1, (d_conv - 1) * conv_dim)``, a slot's ONE row
    (``blocks.Mamba2``), the SSD states ``(Mamba layers, slots
    + 1, H, P, N)`` and the device's own counts (``ops.moe.counters_shape``).
    A table row is ``[slot, block table...]``, slot 0 and block 0 the trash a
    dead decode row and a padded chunk row write; a dead row has no pair in
    the expert layer and counts nowhere."""

    def __init__(self, cfg: NemotronHConfig):
        self.cfg = cfg
        self.dt = jnp.dtype(cfg.dtype)
        self.ssm = cfg.mixer()

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
            return params["embed"]["tokens"][tokens].astype(jnp.float32)

    def lm_head(self, params, h):
        """The untied head on the normed stream."""
        with jax.named_scope("lm_head"):
            y = rmsnorm(h, params["ln_f"]["scale"], self.cfg.norm_eps).astype(self.dt)
            return dot32(y, params["lm_head"]["kernel"])

    def _norm(self, h, layer, which: str):
        return rmsnorm(h, layer[which]["scale"], self.cfg.norm_eps)

    def _qkv(self, u, layer):
        """q (n, H, e), k, v (n, K, e) in the compute dtype; NO rotary."""
        cfg, n = self.cfg, u.shape[0]
        with jax.named_scope("qkv"):
            a = u.astype(self.dt)
            q = dot32(a, layer["q"]["kernel"]).reshape(n, cfg.n_heads, cfg.head_dim)
            k = dot32(a, layer["k"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            v = dot32(a, layer["v"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            return q.astype(self.dt), k.astype(self.dt), v.astype(self.dt)

    def _attn_out(self, h, layer, att):
        with jax.named_scope("attn_out"):
            return h + dot32(att.astype(self.dt).reshape(h.shape[0], -1), layer["o"]["kernel"])

    def _routed(self, h, layer, live, counts, phase: str, experts, index):
        """An ``E`` layer's normed input in the products' dtype, and the
        routed part this chip holds of it.  ``counts`` (``ops.moe``'s ledger)
        gets this layer through ``count_routed``.  ``experts``: the held
        experts of every expert layer, flat, this layer's from ``index *
        experts_held``."""
        cfg = self.cfg
        with jax.named_scope("moe_router"):
            y32 = self._norm(h, layer, "ln2")
            chosen, weights = route(
                y32, layer["router"]["kernel"], layer["router"]["bias"], cfg.experts_per_tok,
                cfg.routed_scaling, eps=cfg.route_eps)
            mask, wmat = held_pairs(chosen, weights, cfg.expert_offset, cfg.experts_held, live)
            counts = count_routed(counts, mask, phase)
        y = y32.astype(self.dt)
        with jax.named_scope("moe_experts"):
            routed = expert_layer(y, mask, wmat, experts["up"], experts["down"],
                                  first=index * cfg.experts_held, top_k=cfg.experts_per_tok,
                                  impl=cfg.attn_impl)
        return y, routed, counts

    def _expert_mlp(self, h, layer, live, counts, phase: str, experts, index):
        """An ``E`` layer: the routed part (``_routed``) and the shared
        expert."""
        y, routed, counts = self._routed(h, layer, live, counts, phase, experts, index)
        sh = layer["shared"]
        with jax.named_scope("moe_shared"):
            return h + routed + relu2(y, sh["up"], sh["down"]), counts

    def _layers(self, params, x, arrays, live, phase: str, slots, ssm, attend):
        """``blocks.pattern_layers`` over the runs of pairs: a step's layers,
        written once for both steps.  ``ssm(u, layer, conv, ssd, at)`` is the
        step's Mamba-2 step (``self.ssm.decode`` / ``.chunk``), ``attend`` its
        paged K/V step, ``slots`` its slot(s) of state, ``live`` its rows that
        count."""
        experts = params["experts"]
        n_blocks, n_slots = arrays[0].shape[1], arrays[2].shape[1]

        def mamba(h, layer, k_pool, v_pool, conv, ssd, l):
            at = l * n_slots + slots
            with jax.named_scope("ssm"):
                y, conv, ssd = ssm(self._norm(h, layer, "ln1"), layer, conv, ssd, at)
            return h + y, k_pool, v_pool, conv, ssd

        def attention(h, layer, k_pool, v_pool, conv, ssd, l):
            base = l * n_blocks
            q, k, v = self._qkv(self._norm(h, layer, "ln1"), layer)
            att, k_pool, v_pool = attend(q, k, v, k_pool, v_pool, base)
            return self._attn_out(h, layer, att), k_pool, v_pool, conv, ssd

        closing = lambda h, layer, counts, m: self._expert_mlp(  # noqa: E731
            h, layer, live, counts, phase, experts, m)
        return pattern_layers(
            self.cfg.runs(), params["runs"], x, arrays,
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
