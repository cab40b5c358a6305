"""AFMoE (arcee-ai/Trinity-Large-Preview, ``model_type`` ``afmoe``) as one chip
of eight that share each layer serves it: grouped-query attention layers of
TWO kinds in one model, three ``sliding_attention`` layers (a window of 4,096
keys, rotary) to one ``full_attention`` layer (every key, NO positional
encoding), a sigmoid GATE on every attention's output, four norms a layer, and
after the leading dense layers an expert layer of which this chip holds a share.

``h`` is the float32 residual stream, ``RMSNorm`` has a learned scale at
``norm_eps``; no bias anywhere::

    h   = E[token] * sqrt(d_model)                       (``mup_enabled``)
    h  += N2(Attn(N1(h)))                                (the sandwich: a norm
    h  += N4(FF(N3(h)))                                   before AND after each part)
    logits = N_f(h) W_head                               (the head is untied)

* **Attn(x)**: ``q = x W_q`` (``H`` heads of ``e``), ``k = x W_k``, ``v = x
  W_v`` (``K`` heads), ``g = x W_g`` (``H x e``).  ``q`` and ``k`` are each
  RMSNorm'ed over their ``e`` lanes with a learned scale; on a
  ``sliding_attention`` layer both THEN turn by the half-split rotary over all
  ``e`` lanes at ``rope_theta``, on a ``full_attention`` layer nothing turns.
  Scores ``q . k / sqrt(e)``, causal, and on a sliding layer query ``i`` sees
  key ``j`` only where ``i - j < window``; softmax in float32, times ``v``,
  ``H / K`` query heads a key-value head.  ``out = (o * sigmoid(g)) W_o``.
* **FF of the first ``n_dense_layers``**: ``(silu(y W_1) * (y W_3)) W_2`` at
  ``d_ff``.  **FF of every later layer**: ``p = sigmoid(y W_r)`` over ALL
  ``n_routed_experts`` in float32; the ``experts_per_tok`` largest of ``p + b``
  chosen (``b`` chooses and does not weigh; one group); ``w = p[chosen] / (sum
  p[chosen] + route_eps) * routed_scaling`` (``ops.moe.route``); ``sum over
  chosen AND held e of w_e Expert_e(y) + Shared(y)``, each a SwiGLU at
  ``d_expert``, droplessly.  This chip holds experts ``expert_offset ..
  expert_offset + experts_held``, one of ``expert_parallel`` that share each
  layer; the router, the shared expert and the attention are whole.

What a sequence holds on the device (``llm.cache.LayerTypedPool``,
``cache_kind`` ``"windowed"``) is split by layer kind: every block of K and V
in the full layers, and in the window layers only the blocks a later query can
still see: the pool hands the blocks behind the window back, the paged
attention starts at the first block a row still holds (``ops.gqa_attention``,
``window``) and a released block is never read.  The layer loop is
``blocks.pattern_layers`` over runs of one (attention kind, feed-forward) kind;
the paged K/V step is ``models.blocks``' (scope ``window_attention`` on the
window layers, ``gqa_attention`` on the full ones, ``chunk_attention`` in a
chunk), the routed layer and its ledger ``ops.moe``'s (``counters`` is what
the programs count on the device, ``stats()["moe"]``); the gate, the QK-norm,
the rotary on one kind alone and the sandwich are HERE.
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
from ray_tpu.ops.moe import (
    count_routed, counters_shape, expert_layer, held_pairs, read_counters, route, swiglu)

#: published layers 5-9: the last leading dense layer, then a whole period
LAYERS_5_TO_9 = ("sliding_attention", "sliding_attention", "full_attention",
                 "sliding_attention", "sliding_attention")
NORMS = ("ln1", "ln2", "ln3", "ln4")


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 25024
    seq_len: int = 262144
    d_model: int = 3072
    #: the published 60 cut to layers 5-9; ``layer_types`` names each layer's
    #: attention, the first ``n_dense_layers`` close with a dense MLP and the
    #: others with the expert layer
    n_layers: int = 5
    layer_types: tuple = LAYERS_5_TO_9
    n_dense_layers: int = 1
    n_heads: int = 48
    n_kv_heads: int = 8
    head_dim: int = 128
    #: keys a ``sliding_attention`` query sees, itself included
    window: int = 4096
    d_ff: int = 12288
    d_expert: int = 3072
    #: the router's width, as published; of them this chip holds
    #: ``experts_held`` from ``expert_offset``, one of ``expert_parallel``
    #: chips that share each layer
    n_routed_experts: int = 256
    experts_held: int = 32
    expert_offset: int = 0
    expert_parallel: int = 8
    experts_per_tok: int = 4
    routed_scaling: float = 2.448
    route_eps: float = 1e-20
    norm_eps: float = 1e-5
    rope_theta: float = 1e4
    #: the initializer's spread of the embedding, of the attention scores
    #: (through the query norm's scale), and what the ROUTED experts' ``W_2``
    #: is scaled by
    init_range: float = 0.02
    score_spread: float = 4.0
    expert_out_gain: float = 0.25
    dtype: str = "bfloat16"
    attn_impl: str = "auto"

    #: what a sequence holds on the device (``llm.engine``): blocks of two
    #: layer kinds, the window layers' handed back behind the window
    cache_kind = "windowed"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", pattern_of(
            self.layer_types, self.n_layers, ("sliding_attention", "full_attention")))
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError("the dense layers lead, and an expert layer follows them")
        if self.n_heads % self.n_kv_heads or self.window < 1:
            raise ValueError("query heads come in whole groups, and a window holds a key")
        check_share(
            self.n_routed_experts, self.expert_offset, self.experts_held, self.experts_per_tok)

    def n_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    def runs(self) -> tuple:
        """The layers as runs of one kind: ``((attention, feed-forward, how
        many), ...)``, the feed-forward ``dense`` or ``moe``."""
        kinds = [(mixer, "dense" if i < self.n_dense_layers else "moe")
                 for i, mixer in enumerate(self.layer_types)]
        return tuple((*kind, n) for kind, n in runs_of(kinds))

    def serving_body(self) -> "AfmoeBody":
        return AfmoeBody(self)


def afmoe_init(rng: jax.Array, cfg: AfmoeConfig) -> dict:
    """Seeded random parameters, made IN ``cfg.dtype`` a layer (an expert) at
    a time (float32 masters of 4.3B parameters would be 17 GB).
    ``params["runs"][i]`` holds run ``i``'s layers stacked (the attention, the
    four norms, and its dense MLP or its router and shared expert),
    ``params["experts"]`` EVERY expert layer's held experts flat, the ``m``-th
    expert layer's from ``m * experts_held``.

    Every projection normal at ``fan_in ** -0.5``, the gate's and the router's
    too (on a normed input the gate's logits and the router's are about N(0,
    1): a gate around a half that differs lane by lane, near-uniform routing,
    the selection bias zero), norm scales 1, but for what the configuration
    file's ``assumed`` explains: the embedding normal at ``init_range`` (times
    ``sqrt(d_model)`` it enters the stream at about a unit, as each part's
    closing norm makes what the part adds); the untied head at ``d ** -0.5``
    (logits of spread 1); the QUERY norm's scale at ``score_spread`` (normed
    ``q`` and ``k`` score ``q . k / sqrt(e)`` at a spread of exactly 1,
    whatever ``W_q`` and ``W_k`` are: the softmax is then near the values'
    mean over thousands of keys and neither the window's edge nor what the
    K/V cache holds reaches the logits; the norm's learned scale is where a
    trained model sets its scores' spread); the ROUTED experts' ``W_2`` times
    ``expert_out_gain`` beside a shared expert at 1 (this chip adds the HELD
    experts' part alone, so a flipped routing choice, which bfloat16 products
    upstream of the router make where two scores lie close, takes a whole
    ``w_e Expert_e(y)`` out of the layer's sum or puts one in; the closing
    norm rescales the sum, so what counts is the routed part's share of it)."""
    d, dt, e = cfg.d_model, jnp.dtype(cfg.dtype), cfg.head_dim
    hq, hkv = cfg.n_heads * e, cfg.n_kv_heads * e
    normal = functools.partial(normal_layers, dtype=dt)
    mlp = functools.partial(gated_mlp_init, d=d, make=normal)

    def layers(key, n: int, ff: str) -> dict:
        ks = jax.random.split(key, 8)
        out = {name: {"scale": jnp.ones((n, d), dt)} for name in NORMS}
        out.update(
            q={"kernel": normal(ks[0], n, (d, hq), d**-0.5)},
            k={"kernel": normal(ks[1], n, (d, hkv), d**-0.5)},
            v={"kernel": normal(ks[2], n, (d, hkv), d**-0.5)},
            gate={"kernel": normal(ks[3], n, (d, hq), d**-0.5)},
            o={"kernel": normal(ks[4], n, (hq, d), hq**-0.5)},
            q_norm={"scale": jnp.full((n, e), cfg.score_spread, dt)},
            k_norm={"scale": jnp.ones((n, e), dt)},
        )
        if ff == "dense":
            return dict(out, mlp=mlp(ks[5], n, width=cfg.d_ff))
        return dict(
            out,
            router={"kernel": normal(ks[5], n, (d, cfg.n_routed_experts), d**-0.5),
                    "bias": jnp.zeros((n, cfg.n_routed_experts), jnp.float32)},
            shared=mlp(ks[6], n, width=cfg.d_expert))

    runs = cfg.runs()
    ks = jax.random.split(rng, len(runs) + 3)
    return {
        "embed": {"tokens": normal(ks[0], 1, (cfg.vocab_size, d), cfg.init_range)[0]},
        "lm_head": {"kernel": normal(ks[1], 1, (d, cfg.vocab_size), d**-0.5)[0]},
        "runs": [layers(k, n, ff) for k, (_, ff, n) in zip(ks[3:], runs)],
        "experts": mlp(ks[2], cfg.n_expert_layers * cfg.experts_held, width=cfg.d_expert,
                       out_gain=cfg.expert_out_gain),
        "ln_f": {"scale": jnp.ones((d,), dt)},
    }


class AfmoeBody:
    """The family's traced layer programs for ``HybridModelRunner``.
    ``arrays`` is ``(k_full, v_full, k_window, v_window, counters)``: K and V
    of each kind ``(layers of the kind, that kind's blocks, K, block, e)``
    and the device's own counts (``ops.moe.counters_shape``).  A table row is
    the full layers' block table followed by the window layers', both by
    logical block, block 0 of each kind the trash a dead decode row and a
    padded chunk row write (and what a released window entry names); a dead
    row has no pair in the expert layer and counts nowhere."""

    def __init__(self, cfg: AfmoeConfig):
        self.cfg = cfg
        self.dt = jnp.dtype(cfg.dtype)

    # -- what the pools hold ----------------------------------------------

    def kv_layout(self) -> dict:
        """Two kinds of layer, each a key-value head a head: ``kinds`` how
        many layers of each, ``window`` the keys a window layer's query sees."""
        cfg = self.cfg
        return {"kinds": {"full": cfg.n_of("full_attention"),
                          "window": cfg.n_of("sliding_attention")},
                "window": cfg.window, "n_heads": cfg.n_kv_heads,
                "head_dim": cfg.head_dim, "dtype": cfg.dtype}

    def state_leaves(self, block_size: int) -> dict:
        return {}

    def counters(self) -> tuple:
        return counters_shape(self.cfg.experts_held)

    read_counters = staticmethod(read_counters)

    # -- shared layer math --------------------------------------------------

    def embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["embed"]["tokens"][tokens].astype(jnp.float32) * self.cfg.d_model**0.5

    def lm_head(self, params, h):
        """The untied head on the normed stream."""
        with jax.named_scope("lm_head"):
            y = rmsnorm(h, params["ln_f"]["scale"], self.cfg.norm_eps).astype(self.dt)
            return dot32(y, params["lm_head"]["kernel"])

    def _norm(self, h, layer, which: str):
        return rmsnorm(h, layer[which]["scale"], self.cfg.norm_eps)

    def _turn(self, x, positions):
        """What a ``sliding_attention`` layer does to its normed q and k (a
        ``full_attention`` layer does nothing)."""
        return rotary_half(x, positions, self.cfg.rope_theta)

    def _qkv(self, h, layer, positions, turns: bool):
        """q (n, H, e), k, v (n, K, e) in the compute dtype, q and k normed
        over their ``e`` and, where the layer ``turns``, rotated THEN; the
        gate's logits (n, H * e) float32."""
        cfg, n = self.cfg, h.shape[0]
        with jax.named_scope("qkv"):
            a = self._norm(h, layer, "ln1").astype(self.dt)
            q = dot32(a, layer["q"]["kernel"]).reshape(n, cfg.n_heads, cfg.head_dim)
            k = dot32(a, layer["k"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            v = dot32(a, layer["v"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            q = rmsnorm(q, layer["q_norm"]["scale"], cfg.norm_eps)
            k = rmsnorm(k, layer["k_norm"]["scale"], cfg.norm_eps)
            if turns:
                q, k = self._turn(q, positions), self._turn(k, positions)
            return (q.astype(self.dt), k.astype(self.dt), v.astype(self.dt),
                    dot32(a, layer["gate"]["kernel"]))

    def _gate(self, att, g):
        """The attention's result (n, H, e) under its gate: (n, H * e)."""
        return att.astype(jnp.float32).reshape(g.shape) * jax.nn.sigmoid(g)

    def _attn_out(self, h, layer, att, g):
        with jax.named_scope("attn_out"):
            out = dot32(self._gate(att, g).astype(self.dt), layer["o"]["kernel"])
            return h + self._norm(out, layer, "ln2")

    def _dense_mlp(self, h, layer):
        with jax.named_scope("dense_mlp"):
            y, w = self._norm(h, layer, "ln3").astype(self.dt), layer["mlp"]
            return h + self._norm(swiglu(y, w["gate"], w["up"], w["down"]), layer, "ln4")

    def _expert_mlp(self, h, layer, live, counts, phase: str, experts, index):
        """The expert layer's part this chip holds, and the shared expert.
        ``counts`` (``ops.moe``'s ledger) gets this layer through
        ``count_routed``.  ``experts``: the held experts of every expert
        layer, flat, this layer's from ``index * experts_held``."""
        cfg = self.cfg
        with jax.named_scope("moe_router"):
            y32 = self._norm(h, layer, "ln3")
            chosen, weights = route(
                y32, layer["router"]["kernel"], layer["router"]["bias"], cfg.experts_per_tok,
                cfg.routed_scaling, eps=cfg.route_eps)
            mask, wmat = held_pairs(chosen, weights, cfg.expert_offset, cfg.experts_held, live)
            counts = count_routed(counts, mask, phase)
        y, sh = y32.astype(self.dt), layer["shared"]
        with jax.named_scope("moe_experts"):
            routed = expert_layer(
                y, mask, wmat, experts["gate"], experts["up"], experts["down"],
                first=index * cfg.experts_held, top_k=cfg.experts_per_tok, impl=cfg.attn_impl)
        with jax.named_scope("moe_shared"):
            out = routed + swiglu(y, sh["gate"], sh["up"], sh["down"])
            return h + self._norm(out, layer, "ln4"), counts

    def _layers(self, params, x, arrays, live, phase: str, positions, attend_full, attend_window):
        """``blocks.pattern_layers`` over the runs: a step's layers, written
        once for both steps.  ``attend_full`` / ``attend_window`` are the
        step's paged K/V steps over each kind's pools and tables, ``live`` its
        rows that count."""
        full_blocks, window_blocks = arrays[0].shape[1], arrays[2].shape[1]
        experts = params["experts"]

        def full(h, layer, kf, vf, kw, vw, l):
            q, k, v, g = self._qkv(h, layer, positions, turns=False)
            att, kf, vf = attend_full(q, k, v, kf, vf, l * full_blocks)
            return self._attn_out(h, layer, att, g), kf, vf, kw, vw

        def sliding(h, layer, kf, vf, kw, vw, l):
            q, k, v, g = self._qkv(h, layer, positions, turns=True)
            att, kw, vw = attend_window(q, k, v, kw, vw, l * window_blocks)
            return self._attn_out(h, layer, att, g), kf, vf, kw, vw

        closings = {
            "dense": lambda h, layer, counts, m: (self._dense_mlp(h, layer), counts),
            "moe": lambda h, layer, counts, m: self._expert_mlp(
                h, layer, live, counts, phase, experts, m)}
        return pattern_layers(
            self.cfg.runs(), params["runs"], x, arrays,
            {"full_attention": full, "sliding_attention": sliding}, closings, phase)

    def decode(self, params, x, arrays, positions, tables):
        """One token of many sequences.  x: (S, d) embedded tokens at
        ``positions``; tables: (S, 2 T).  Returns (hidden (S, d), arrays)."""
        cfg, t = self.cfg, tables.shape[1] // 2
        attend_full = paged_kv_decode(arrays[0], tables[:, :t], positions, cfg.attn_impl)
        attend_window = paged_kv_decode(
            arrays[2], tables[:, t:], positions, cfg.attn_impl, window=cfg.window)
        return self._layers(
            params, x, arrays, tables[:, 0] > 0, "decode", positions, attend_full, attend_window)

    def chunk(self, params, x, arrays, start, n_valid, table):
        """A prefill chunk.  x: (C, d) embedded tokens of ONE sequence at
        ``start ..``, the first ``n_valid`` real; table: (2 T,).  Returns
        (the last valid token's hidden (1, d), arrays)."""
        t = table.shape[0] // 2
        positions = start + jnp.arange(x.shape[0], dtype=jnp.int32)
        attend_full = paged_kv_chunk(arrays[0], table[:t], positions, start, n_valid)
        attend_window = paged_kv_chunk(
            arrays[2], table[t:], positions, start, n_valid, window=self.cfg.window)
        x, arrays = self._layers(
            params, x, arrays, jnp.arange(x.shape[0]) < n_valid, "chunk", positions,
            attend_full, attend_window)
        return last_valid(x, n_valid), arrays
