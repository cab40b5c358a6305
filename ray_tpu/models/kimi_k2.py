"""Kimi-K2.5's language model (``model_type`` ``kimi_k2``: DeepSeek-V3's
block) as ONE chip of an expert-parallel deployment serves it.

Every layer: ``h += Attention(RMSNorm(h))``, ``h += MLP(RMSNorm(h))``; a
final RMSNorm; an untied head.  ``h`` is float32.

* **Multi-head latent attention.**  ``c_q = RMSNorm(x W_qa)``, ``q = c_q
  W_qb`` as heads of ``[q_nope, q_rope]``; ``[c_kv, k_r] = x W_kva``, ``c =
  RMSNorm(c_kv)``; ``q_rope`` and ``k_r`` rotated at the token's position
  (``k_r`` ONE vector for all heads); ``[k_nope, v] = c W_kvb``; scores
  ``(q_nope . k_nope + q_rope . k_r) * scale``, causal softmax, times ``v``,
  then ``W_o``.  What a token leaves in the cache is ``[c, rot(k_r)]``: one
  row a layer, no head axis, no values (``ops.latent_attention``).  A decode
  attends in the ABSORBED form (``q_nope W_kvb^K`` against the rows, the
  result expanded by ``W_kvb^V``); a prefill chunk in the EXPANDED form (at
  512 queries against 17k rows it needs half the operations).  Each is a
  Pallas kernel on a TPU and plain XLA elsewhere (``attn_impl``).
* **YaRN** frequencies (``yarn_inv_freq``) and the softmax scale
  (``softmax_scale``: ``mscale_all_dim`` squares into it); rotary in the
  half-split form (lanes ``[0:r/2]`` with ``[r/2:r]``): the published code's
  interleaved-to-halves permutation is a relabelling of ``W_qb`` / ``W_kva``
  columns under seeded weights.
* **Layers below ``n_dense_layers``**: a SwiGLU of width ``d_ff``.
* **The others**: ``sigmoid`` router over ALL ``n_routed_experts``, the top
  ``experts_per_tok`` of ``score + bias`` (the bias chooses, it does not
  weigh), weights normalised and times ``routed_scaling_factor``; plus one
  shared expert.  THIS CHIP holds experts ``expert_offset .. expert_offset +
  experts_held`` (one of ``expert_parallel`` chips that share each layer) and
  adds their part alone, droplessly (``ops.moe``); the absent experts' part
  is the other chips' and is left out, here and in the plain reference.
  ``vocab_size`` is the slice of the vocabulary held here.

Everything of the family is HERE: the configuration, the seeded initializer
and the layer programs ``llm.state_runner.HybridModelRunner`` takes through
``serving_body()``.  The body holds NO state beside its blocks, so the
engine shares, forks and evicts them as it does GPT-J's: the radix prefix
cache runs on latent blocks.  ``counters`` is what the programs count on the
device (``ops.moe``'s ledger of the routed layer: the router's load is known
nowhere else): it rides every step beside the pool and is fetched by
``LLMEngine.stats()`` alone.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.model_runner import _carry_loop, _chunk_write, _slots_write
from ray_tpu.models.blocks import (
    check_share, dot32, gated_mlp_init, last_valid, normal_layers, rmsnorm)
from ray_tpu.models.blocks import rmsnorm as _rmsnorm  # noqa: F401  the benchmark's controls' name
from ray_tpu.ops.latent_attention import (
    latent_chunk_attention,
    latent_decode_attention,
    padded_width,
)
from ray_tpu.ops.moe import (
    count_routed, count_step, counters_shape, expert_layer, held_pairs, read_counters, route,
    swiglu)


@dataclasses.dataclass(frozen=True)
class KimiK2Config:
    #: the slice of the published 163,840 rows held here (embedding and head)
    vocab_size: int = 20480
    seq_len: int = 262144
    d_model: int = 7168
    #: the published 61 cut to 1 dense + 6 expert layers (pipeline stages
    #: hold the rest)
    n_layers: int = 7
    n_dense_layers: int = 1
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 18432
    d_expert: int = 2048
    #: the router's width, as published; of them this chip holds
    #: ``experts_held`` from ``expert_offset``, one of ``expert_parallel``
    #: chips that share each layer
    n_routed_experts: int = 384
    experts_held: int = 12
    expert_offset: int = 0
    expert_parallel: int = 32
    experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.827
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_factor: float = 64.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    init_range: float = 0.02
    dtype: str = "bfloat16"
    attn_impl: str = "auto"

    #: what a sequence holds on the device (``llm.engine``): blocks of what
    #: the family's body says a token leaves behind, and nothing beside them
    cache_kind = "paged"

    def __post_init__(self):
        if not 0 < self.n_dense_layers < self.n_layers:
            raise ValueError("at least one dense and one expert layer")
        check_share(
            self.n_routed_experts, self.expert_offset, self.experts_held, self.experts_per_tok)

    @property
    def head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def serving_body(self) -> "KimiK2Body":
        return KimiK2Body(self)


def yarn_inv_freq(cfg: KimiK2Config) -> np.ndarray:
    """(rope / 2,) float32: ``f_i = theta^(-2i / rope)``, divided by
    ``factor`` where the ramp between the correction dims of ``beta_fast``
    and ``beta_slow`` says so."""
    dim = cfg.qk_rope_head_dim
    f = cfg.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations: float) -> float:
        return dim * math.log(cfg.rope_original_max_position / (rotations * 2 * math.pi)) / (
            2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f * (1 - ramp) + f / cfg.rope_factor * ramp).astype(np.float32)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: KimiK2Config) -> float:
    """``head_dim^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``."""
    return cfg.head_dim ** -0.5 * _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2


def rotary_scale(cfg: KimiK2Config) -> float:
    """What cos and sin are multiplied by (1 at the published values)."""
    return (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
            / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))


def kimi_k2_init(rng: jax.Array, cfg: KimiK2Config) -> dict:
    """Seeded random parameters, made IN ``cfg.dtype`` a layer at a time
    (float32 masters of 4.85B parameters would be 19.4 GB).  Every
    projection normal at ``fan_in ** -0.5``, the router's too (on a normed
    input its scores are about N(0, 1): near-uniform routing); the selection
    bias 0; norm scales 1; the embedding normal at ``init_range``."""
    d, h, dt = cfg.d_model, cfg.n_heads, jnp.dtype(cfg.dtype)
    nd, nm = cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers

    def normal(key, layers: int, shape: tuple, fan_in: int):
        return normal_layers(key, layers, shape, fan_in**-0.5, dt)

    def attention(key, n: int) -> dict:
        ks = jax.random.split(key, 6)
        r = cfg.kv_lora_rank
        return {
            "ln1": {"scale": jnp.ones((n, d), dt)},
            "q_a": {"kernel": normal(ks[0], n, (d, cfg.q_lora_rank), d)},
            "q_a_norm": {"scale": jnp.ones((n, cfg.q_lora_rank), dt)},
            "q_b": {"kernel": normal(ks[1], n, (cfg.q_lora_rank, h * cfg.head_dim),
                                     cfg.q_lora_rank)},
            "kv_a": {"kernel": normal(ks[2], n, (d, r + cfg.qk_rope_head_dim), d)},
            "kv_a_norm": {"scale": jnp.ones((n, r), dt)},
            # the published kv_b's columns, a head's keys and a head's values,
            # as two matrices (rank, heads, width)
            "kv_b_k": {"kernel": normal(ks[3], n, (r, h, cfg.qk_nope_head_dim), r)},
            "kv_b_v": {"kernel": normal(ks[4], n, (r, h, cfg.v_head_dim), r)},
            "o": {"kernel": normal(ks[5], n, (h * cfg.v_head_dim, d), h * cfg.v_head_dim)},
            "ln2": {"scale": jnp.ones((n, d), dt)},
        }

    def mlp(key, n: int, lead: tuple, width: int) -> dict:
        """``n`` layers' gated MLPs, ``lead`` an axis of experts before each matrix's own."""
        return gated_mlp_init(key, n, d, width, lambda k, n, shape, std: normal_layers(
            k, n, lead + shape, std, dt))

    ks = jax.random.split(rng, 8)
    return {
        "embed": {"tokens": (cfg.init_range * jax.random.normal(
            ks[0], (cfg.vocab_size, d), jnp.float32)).astype(dt)},
        "dense": dict(attention(ks[1], nd), mlp=mlp(ks[2], nd, (), cfg.d_ff)),
        "moe": dict(
            attention(ks[3], nm),
            router={"kernel": normal(ks[4], nm, (d, cfg.n_routed_experts), d),
                    "bias": jnp.zeros((nm, cfg.n_routed_experts), dt)},
            experts=mlp(ks[5], nm, (cfg.experts_held,), cfg.d_expert),
            shared=mlp(ks[6], nm, (), cfg.n_shared_experts * cfg.d_expert),
        ),
        "ln_f": {"scale": jnp.ones((d,), dt)},
        "lm_head": {"kernel": normal(ks[7], 1, (d, cfg.vocab_size), d)[0]},
    }


class KimiK2Body:
    """The family's traced layer programs for ``HybridModelRunner``.
    ``arrays`` is ``(pool, counters)``: the latent pool ``(L, blocks, 1,
    block, width)`` and the device's own counts ``(1, len(ops.moe.COUNTERS) +
    experts_held)`` int32.  A table row is the sequence's block table; block
    0 is the trash a dead decode row and a padded chunk row write, and a row
    whose first block is 0 is dead: it has no pair in the expert layer and
    counts nowhere."""

    def __init__(self, cfg: KimiK2Config):
        self.cfg = cfg
        self.dt = jnp.dtype(cfg.dtype)
        self.rank, self.rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        self.width = padded_width(self.rank, self.rope)
        self.scale = softmax_scale(cfg)
        self.inv_freq = yarn_inv_freq(cfg)
        self.rot_scale = rotary_scale(cfg)

    # -- what the engine allocates ------------------------------------------

    def kv_layout(self) -> dict:
        """ONE array of latent rows a layer: no head axis (1), no values."""
        return {"n_layers": self.cfg.n_layers, "n_heads": 1, "head_dim": self.width,
                "dtype": self.cfg.dtype, "values": False}

    def state_leaves(self, block_size: int) -> dict:
        return {}

    def counters(self) -> tuple:
        return counters_shape(self.cfg.experts_held)

    read_counters = staticmethod(read_counters)

    # -- shared layer math ----------------------------------------------------

    def embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["embed"]["tokens"][tokens].astype(jnp.float32)

    def lm_head(self, params, h):
        with jax.named_scope("lm_head"):
            y = rmsnorm(h, params["ln_f"]["scale"], self.cfg.rms_norm_eps).astype(self.dt)
            return dot32(y, params["lm_head"]["kernel"])

    def _rotate(self, x, positions):
        """Half-split rotary of (n, ..., rope) float32 at ``positions`` (n,)."""
        ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(self.inv_freq)[None, :]
        cos, sin = jnp.cos(ang) * self.rot_scale, jnp.sin(ang) * self.rot_scale
        shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (self.rope // 2,)
        cos, sin = cos.reshape(shape), sin.reshape(shape)
        x1, x2 = x[..., :self.rope // 2], x[..., self.rope // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def _project(self, x, layer, positions):
        """The four down / up projections of a token: (q_nope (n, H, dn) and
        q_rope (n, H, dr) in the compute dtype, the token's cache row (n,
        width))."""
        cfg, dt, n = self.cfg, self.dt, x.shape[0]
        y = rmsnorm(x, layer["ln1"]["scale"], cfg.rms_norm_eps).astype(dt)
        c_q = rmsnorm(dot32(y, layer["q_a"]["kernel"]), layer["q_a_norm"]["scale"],
                       cfg.rms_norm_eps).astype(dt)
        q = dot32(c_q, layer["q_b"]["kernel"]).reshape(n, cfg.n_heads, cfg.head_dim)
        q_nope = q[..., :cfg.qk_nope_head_dim].astype(dt)
        q_rope = self._rotate(q[..., cfg.qk_nope_head_dim:], positions).astype(dt)
        kv = dot32(y, layer["kv_a"]["kernel"])
        c = rmsnorm(kv[:, :self.rank], layer["kv_a_norm"]["scale"], cfg.rms_norm_eps)
        k_r = self._rotate(kv[:, self.rank:], positions)
        row = jnp.concatenate(
            [c, k_r, jnp.zeros((n, self.width - self.rank - self.rope))], axis=-1)
        return q_nope, q_rope, row.astype(dt)

    def _attn_out(self, x, layer, o):
        """o: (n, H, dv) float32 -> the residual after ``W_o``."""
        return x + dot32(o.astype(self.dt).reshape(x.shape[0], -1), layer["o"]["kernel"])

    def _decode_attention(self, x, layer, pool, base, positions, tables, write):
        with jax.named_scope("mla_proj"):
            q_nope, q_rope, row = self._project(x, layer, positions)
            # the absorb: a head's keys' up-projection goes into its query
            q_lat = jnp.einsum("nhd,rhd->nhr", q_nope, layer["kv_b_k"]["kernel"].astype(self.dt),
                               preferred_element_type=jnp.float32)
            pad = jnp.zeros(q_rope.shape[:2] + (self.width - self.rank - self.rope,), self.dt)
            q_abs = jnp.concatenate([q_lat.astype(self.dt), q_rope, pad], axis=-1)
        with jax.named_scope("latent_write"):
            pool = write(pool, row[:, None, :].astype(pool.dtype), base)
        with jax.named_scope("latent_attention"):
            o_lat = latent_decode_attention(
                q_abs.astype(pool.dtype), pool.reshape((-1,) + pool.shape[2:]), tables + base,
                positions, rank=self.rank, scale=self.scale, impl=self.cfg.attn_impl)
        with jax.named_scope("mla_proj"):
            o = jnp.einsum("nhr,rhd->nhd", o_lat.astype(self.dt),
                           layer["kv_b_v"]["kernel"].astype(self.dt),
                           preferred_element_type=jnp.float32)
            return self._attn_out(x, layer, o), pool

    def _chunk_attention(self, x, layer, pool, base, positions, table, write):
        with jax.named_scope("mla_proj"):
            q_nope, q_rope, row = self._project(x, layer, positions)
        with jax.named_scope("latent_write"):
            pool = write(pool, row[:, None, :].astype(pool.dtype), base)
        with jax.named_scope("latent_attention"):
            o = latent_chunk_attention(
                q_nope.astype(pool.dtype), q_rope.astype(pool.dtype),
                pool.reshape((-1,) + pool.shape[2:]), table + base, positions,
                layer["kv_b_k"]["kernel"], layer["kv_b_v"]["kernel"],
                rank=self.rank, scale=self.scale, impl=self.cfg.attn_impl)
        with jax.named_scope("mla_proj"):
            return self._attn_out(x, layer, o), pool

    def _dense_mlp(self, h, layer):
        with jax.named_scope("mlp"):
            y = rmsnorm(h, layer["ln2"]["scale"], self.cfg.rms_norm_eps).astype(self.dt)
            mlp = layer["mlp"]
            return h + swiglu(y, mlp["gate"], mlp["up"], mlp["down"])

    def _expert_mlp(self, h, layer, live, counts, phase: str, experts=None, index=0):
        """The expert layer's part this chip holds, and the shared expert.
        ``counts`` (``ops.moe``'s ledger) gets this layer through
        ``count_routed``.  ``experts``: the held experts of every layer, flat,
        this layer's from ``index * experts_held`` (None: the layer's own,
        ``layer["experts"]``)."""
        cfg = self.cfg
        with jax.named_scope("moe_router"):
            y32 = rmsnorm(h, layer["ln2"]["scale"], cfg.rms_norm_eps)
            chosen, weights = route(
                y32, layer["router"]["kernel"], layer["router"]["bias"],
                cfg.experts_per_tok, cfg.routed_scaling_factor)
            mask, wmat = held_pairs(chosen, weights, cfg.expert_offset, cfg.experts_held, live)
            counts = count_routed(counts, mask, phase)
        y = y32.astype(self.dt)
        ex, sh = experts or layer["experts"], layer["shared"]
        with jax.named_scope("moe_experts"):
            routed = expert_layer(y, mask, wmat, ex["gate"], ex["up"], ex["down"],
                                  first=index * cfg.experts_held, top_k=cfg.experts_per_tok,
                                  impl=cfg.attn_impl)
        with jax.named_scope("moe_shared"):
            return h + routed + swiglu(y, sh["gate"], sh["up"], sh["down"]), counts

    def _layers(self, params, x, pool, counts, attention, live, phase: str):
        """Both segments over the ONE pool: the dense layers, then the
        expert layers, whose blocks lie ``n_dense_layers`` pools further."""
        blocks = pool.shape[1]
        shift = self.cfg.n_dense_layers * blocks
        # the experts stay OUT of the layer loop's slices: the expert layer
        # indexes every layer's in one flat array (ops.moe.expert_layer)
        moe = {k: v for k, v in params["moe"].items() if k != "experts"}
        experts = {k: v.reshape((-1,) + v.shape[2:]) for k, v in params["moe"]["experts"].items()}

        def dense(x, layer, pool, base):
            x, pool = attention(x, layer, pool, base)
            return self._dense_mlp(x, layer), pool

        def expert(x, layer, pool, counts, base):
            x, pool = attention(x, layer, pool, base + shift)
            x, counts = self._expert_mlp(
                x, layer, live, counts, phase, experts, base // blocks)
            return x, pool, counts

        x, pool = _carry_loop(params["dense"], x, (pool,), dense)
        x, pool, counts = _carry_loop(moe, x, (pool, counts), expert)
        return x, pool, count_step(counts, phase)

    # -- the two step programs -------------------------------------------------

    def decode(self, params, x, arrays, positions, tables):
        """x: (S, d) embedded tokens at ``positions``; tables: (S, T).
        Returns (hidden (S, d), arrays)."""
        pool, counts = arrays
        bs = pool.shape[3]
        phys = jnp.take_along_axis(tables, (positions // bs)[:, None], axis=1)[:, 0]
        write = _slots_write(phys, positions % bs, bs)

        def attention(x, layer, pool, base):
            return self._decode_attention(x, layer, pool, base, positions, tables, write)

        x, pool, counts = self._layers(
            params, x, pool, counts, attention, tables[:, 0] > 0, "decode")
        return x, (pool, counts)

    def chunk(self, params, x, arrays, start, n_valid, table):
        """x: (C, d) embedded tokens of ONE sequence at ``start ..``, the
        first ``n_valid`` real; table: (T,).  Returns (the last valid
        token's hidden (1, d), arrays)."""
        pool, counts = arrays
        chunk, bs = x.shape[0], pool.shape[3]
        positions = start + jnp.arange(chunk, dtype=jnp.int32)
        write = _chunk_write(table, start, n_valid, chunk, bs)

        def attention(x, layer, pool, base):
            return self._chunk_attention(x, layer, pool, base, positions, table, write)

        x, pool, counts = self._layers(
            params, x, pool, counts, attention, jnp.arange(chunk) < n_valid, "chunk")
        return last_valid(x, n_valid), (pool, counts)
