"""Falcon-H1 (tiiuae/Falcon-H1-34B-Instruct, ``model_type`` ``falcon_h1``):
a Mamba-2 mixer AND grouped-query attention IN PARALLEL in every block.

``h`` is the float32 residual stream, ``RMSNorm`` has a learned scale::

    h   = E[token] * embedding_multiplier
    u   = RMSNorm_in(h)
    h  += ssm_out_multiplier * SSM(u)
          + attention_out_multiplier * Attn(u * attention_in_multiplier)
    h  += MLP(RMSNorm_ff(h))
    logits = (RMSNorm_f(h) W_head) * lm_head_multiplier

* **SSM** (Mamba-2, ``ops.ssd``): ``p = ((u * ssm_in_multiplier) W_in) . m``
  with ``W_in``: ``d -> [z d_ssm | x d_ssm | B G N | C G N | dt H]`` and ``m``
  the muP vector, ``ssm_multipliers[0..4]`` laid over those five segments;
  ``[x | B | C]`` through a causal depthwise convolution of width ``d_conv``
  with bias, then SiLU; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``,
  one number a head; the recurrence of ``ops.ssd``; ``y = RMSNorm_grouped(y .
  silu(z))`` within each of the ``G`` groups (gate, THEN norm), learned scale;
  ``SSM = y W_out``.
* **Attention**: ``q = a W_q``, ``k = (a W_k) * key_multiplier``, ``v = a
  W_v``; rotary over the whole head (half-split, ``rope_theta``); causal
  softmax of ``q . k / sqrt(e)``, ``H / K`` query heads a key-value head
  (``ops.gqa_attention``); ``Attn = concat W_o``.
* **MLP**: ``W_down(silu((v W_gate) * mlp_multipliers[0]) . (v W_up)) *
  mlp_multipliers[1]``.  No bias anywhere but the convolution's.

What a sequence holds on the device (``llm.cache.HybridPool``), in EVERY
layer: blocks of K and V, which grow with it, and a slot of fixed-size
state: the SSD state ``(H, P, N)`` float32 and the convolution's last
``d_conv - 1`` inputs.  The configuration, the seeded initializer and the
layer programs ``llm.state_runner.HybridModelRunner`` (which names no
family) takes through ``serving_body()`` are all HERE: one ``_carry_loop``
over the layers with the four pools as its carry.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.model_runner import _carry_loop, _chunk_write, _slots_write
from ray_tpu.ops.gqa_attention import gqa_chunk_attention, gqa_paged_attention, rotary_half
from ray_tpu.ops.ssd import ssd_chunk, ssd_decode


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    #: the slice of the published 261,120 rows held here (embedding and head)
    vocab_size: int = 65280
    seq_len: int = 262144
    d_model: int = 5120
    #: the published 72 cut to 8 (pipeline stages hold the rest)
    n_layers: int = 8
    n_heads: int = 20
    n_kv_heads: int = 4
    head_dim: int = 128
    d_ff: int = 21504
    #: the Mamba-2 mixer: inner width (heads x head size), heads, state
    #: columns, groups of B and C, convolution width, tokens a sub-chunk
    d_ssm: int = 4096
    ssm_heads: int = 32
    d_state: int = 256
    n_groups: int = 2
    d_conv: int = 4
    ssm_chunk: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    #: the muP multipliers, as published
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                              0.3535533905932738)
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    #: the initializer's spread of the attention scores ``q . k / sqrt(e)``
    #: (through ``W_q``), and its ranges of A and of the step size (through
    #: dt_bias)
    score_spread: float = 3.0
    a_min: float = 1.0
    a_max: float = 16.0
    dt_min: float = 1e-3
    dt_max: float = 0.1
    dtype: str = "bfloat16"
    #: the SSD state's dtype.  float32: a bfloat16 state loses the small
    #: steps (dt down to 1e-3) of a state it has integrated
    state_dtype: str = "float32"
    attn_impl: str = "auto"

    #: what a sequence holds on the device (``llm.engine``): blocks of every
    #: layer's K/V AND a slot of fixed-size state
    cache_kind = "hybrid"

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.ssm_heads % self.n_groups:
            raise ValueError("query heads and SSM heads come in whole groups")
        if self.d_ssm % self.ssm_heads:
            raise ValueError("d_ssm must be whole heads")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("five ssm_multipliers (z, x, B, C, dt) and two mlp_multipliers")

    @property
    def ssm_head_dim(self) -> int:
        return self.d_ssm // self.ssm_heads

    @property
    def conv_dim(self) -> int:
        """The channels that go through the convolution: ``[x | B | C]``."""
        return self.d_ssm + 2 * self.n_groups * self.d_state

    def ssm_segments(self) -> tuple:
        """Widths of ``W_in``'s five segments ``(z, x, B, C, dt)``."""
        gn = self.n_groups * self.d_state
        return (self.d_ssm, self.d_ssm, gn, gn, self.ssm_heads)

    def mup_vector(self) -> np.ndarray:
        """``ssm_multipliers`` laid over ``W_in``'s segments: (sum,) float32."""
        return np.concatenate([
            np.full(w, m, np.float32) for w, m in zip(self.ssm_segments(), self.ssm_multipliers)])

    def serving_body(self) -> "FalconH1Body":
        return FalconH1Body(self)


def falcon_h1_init(rng: jax.Array, cfg: FalconH1Config) -> dict:
    """Seeded random parameters, made IN ``cfg.dtype`` a layer at a time
    (float32 masters of 4.1B parameters would be 16.4 GB).  The multipliers
    are muP's and trained weights carry their inverse, so every multiplied
    product is normal at ``fan_in ** -0.5 / its multiplier`` (``W_in``'s five
    segments each by its own): scores, gates and logits are then of order one
    and a fault in the rotary, the cache or the head shows in the logits.  The
    embedding normal at ``1 / embedding_multiplier`` (a unit stream).  ``W_q``
    at ``score_spread`` times that: over thousands of keys a softmax of scores
    of unit spread is all but uniform, its output the values' mean (``N **
    -0.5`` of a unit), and nothing the K/V cache holds would reach the logits;
    at a spread of 3 a query's weight lies on a few keys at every context up
    to 10k, as a trained model's does.  ``A``
    uniform in ``[a_min, a_max]``, the step size log-uniform in ``[dt_min,
    dt_max]`` through ``dt_bias`` (its inverse softplus), ``D`` 1, the
    convolution uniform at ``d_conv ** -0.5`` with no bias, norm scales 1."""
    d, dff, n, dt = cfg.d_model, cfg.d_ff, cfg.n_layers, jnp.dtype(cfg.dtype)
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim

    def normal(key, shape: tuple, std, layers: int = n):
        """(layers,) + shape, one layer at a time; ``std`` a number or a
        vector over the last axis."""
        std = jnp.asarray(std, jnp.float32)
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape, jnp.float32) * std).astype(dt),
            jax.random.split(key, layers))

    ks = jax.random.split(rng, 14)
    in_std = d**-0.5 / (cfg.ssm_in_multiplier * cfg.mup_vector())
    step = jnp.exp(jax.random.uniform(ks[0], (n, cfg.ssm_heads)) * (
        math.log(cfg.dt_max) - math.log(cfg.dt_min)) + math.log(cfg.dt_min))
    blocks = {
        "ln_in": {"scale": jnp.ones((n, d), dt)},
        "ln_ff": {"scale": jnp.ones((n, d), dt)},
        "q": {"kernel": normal(
            ks[1], (d, hq), cfg.score_spread * d**-0.5 / cfg.attention_in_multiplier)},
        "k": {"kernel": normal(ks[2], (d, hkv), d**-0.5 / (
            cfg.attention_in_multiplier * cfg.key_multiplier))},
        "v": {"kernel": normal(ks[3], (d, hkv), d**-0.5 / cfg.attention_in_multiplier)},
        "o": {"kernel": normal(ks[4], (hq, d), hq**-0.5 / cfg.attention_out_multiplier)},
        "ssm_in": {"kernel": normal(ks[5], (d, in_std.shape[0]), in_std)},
        "conv": {"kernel": (jax.random.uniform(
            ks[6], (n, cfg.d_conv, cfg.conv_dim), jnp.float32, -1.0, 1.0)
            * cfg.d_conv**-0.5).astype(dt),
            "bias": jnp.zeros((n, cfg.conv_dim), dt)},
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(
            ks[7], (n, cfg.ssm_heads), jnp.float32, cfg.a_min, cfg.a_max)),
        "D": jnp.ones((n, cfg.ssm_heads), jnp.float32),
        "ssm_norm": {"scale": jnp.ones((n, cfg.d_ssm), dt)},
        "ssm_out": {"kernel": normal(
            ks[8], (cfg.d_ssm, d), cfg.d_ssm**-0.5 / cfg.ssm_out_multiplier)},
        "gate": {"kernel": normal(ks[9], (d, dff), d**-0.5 / cfg.mlp_multipliers[0])},
        "up": {"kernel": normal(ks[10], (d, dff), d**-0.5)},
        "down": {"kernel": normal(ks[11], (dff, d), dff**-0.5 / cfg.mlp_multipliers[1])},
    }
    return {
        "embed": {"tokens": normal(
            ks[12], (cfg.vocab_size, d), 1.0 / cfg.embedding_multiplier, layers=1)[0]},
        "blocks": blocks,
        "ln_f": {"scale": jnp.ones((d,), dt)},
        "lm_head": {"kernel": normal(
            ks[13], (d, cfg.vocab_size), d**-0.5 / cfg.lm_head_multiplier, layers=1)[0]},
    }


def _rmsnorm(x, scale, eps):
    """RMSNorm in float32 (x: the float32 stream, or a float32 product)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _dot32(x, kernel):
    """x @ kernel on x's dtype, float32 out."""
    return jnp.dot(x, kernel.astype(x.dtype), preferred_element_type=jnp.float32)


class FalconH1Body:
    """The family's traced layer programs for ``HybridModelRunner``.  The
    pools ride as ``HybridPool.arrays`` has them: ``(k, v, conv, ssd)``: K and
    V ``(L, blocks, K, block, e)``, the convolution's tails ``(L, slots + 1,
    d_conv - 1, conv_dim)`` and the SSD states ``(L, slots + 1, H, P, N)``.  A
    table row is ``[slot, block table...]``, slot 0 and block 0 the trash a
    dead decode row and a padded chunk row write."""

    def __init__(self, cfg: FalconH1Config):
        self.cfg = cfg
        self.dt = jnp.dtype(cfg.dtype)
        self.mup = cfg.mup_vector()
        seg = np.cumsum(cfg.ssm_segments())
        #: where z ends, and x, B, C end within ``[x | B | C]``
        self.z_end, self.conv_end = int(seg[0]), int(seg[3])
        self.x_end, self.b_end = cfg.d_ssm, cfg.d_ssm + cfg.n_groups * cfg.d_state

    # -- what the pools hold ----------------------------------------------

    def kv_layout(self) -> dict:
        """The paged pool: EVERY layer's K and V, a key-value head a head."""
        cfg = self.cfg
        return {"n_layers": cfg.n_layers, "n_heads": cfg.n_kv_heads,
                "head_dim": cfg.head_dim, "dtype": cfg.dtype}

    def state_leaves(self, block_size: int) -> dict:
        """name -> (layers, one slot's shape, dtype) of the state pool."""
        cfg = self.cfg
        return {
            "conv": (cfg.n_layers, (cfg.d_conv - 1, cfg.conv_dim), cfg.dtype),
            "ssd": (cfg.n_layers, (cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state),
                    cfg.state_dtype),
        }

    # -- shared layer math --------------------------------------------------

    def embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["embed"]["tokens"][tokens].astype(
                jnp.float32) * self.cfg.embedding_multiplier

    def lm_head(self, params, h):
        with jax.named_scope("lm_head"):
            y = _rmsnorm(h, params["ln_f"]["scale"], self.cfg.rms_norm_eps).astype(self.dt)
            return _dot32(y, params["lm_head"]["kernel"]) * self.cfg.lm_head_multiplier

    def _ssm_in(self, u, layer):
        """The input projection and the muP vector: (z (n, d_ssm) float32,
        ``[x | B | C]`` before the convolution in the compute dtype, the step
        size (n, H) float32 after its softplus)."""
        cfg = self.cfg
        p = _dot32((u * cfg.ssm_in_multiplier).astype(self.dt),
                   layer["ssm_in"]["kernel"]) * self.mup
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
        """Gate, THEN the grouped norm, then the output projection."""
        cfg = self.cfg
        gated = y.reshape(z.shape) * jax.nn.silu(z)
        grouped = gated.reshape(z.shape[0], cfg.n_groups, -1)
        normed = grouped * jax.lax.rsqrt(
            (grouped * grouped).mean(-1, keepdims=True) + cfg.rms_norm_eps)
        normed = normed.reshape(z.shape) * layer["ssm_norm"]["scale"].astype(jnp.float32)
        return _dot32(normed.astype(self.dt), layer["ssm_out"]["kernel"])

    def _qkv(self, u, layer, positions):
        """q (n, H, e), k, v (n, K, e) in the compute dtype, q and k rotated."""
        cfg, n = self.cfg, u.shape[0]
        with jax.named_scope("qkv"):
            a = (u * cfg.attention_in_multiplier).astype(self.dt)
            q = _dot32(a, layer["q"]["kernel"]).reshape(n, cfg.n_heads, cfg.head_dim)
            k = (_dot32(a, layer["k"]["kernel"]) * cfg.key_multiplier).reshape(
                n, cfg.n_kv_heads, cfg.head_dim)
            v = _dot32(a, layer["v"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            q = rotary_half(q, positions, cfg.rope_theta).astype(self.dt)
            k = rotary_half(k, positions, cfg.rope_theta).astype(self.dt)
            return q, k, v.astype(self.dt)

    def _close(self, h, layer, ssm, att):
        """Both branches onto the stream, then the MLP."""
        cfg = self.cfg
        with jax.named_scope("attn_out"):
            h = h + cfg.ssm_out_multiplier * ssm + cfg.attention_out_multiplier * _dot32(
                att.astype(self.dt).reshape(h.shape[0], -1), layer["o"]["kernel"])
        with jax.named_scope("mlp"):
            y = _rmsnorm(h, layer["ln_ff"]["scale"], cfg.rms_norm_eps).astype(self.dt)
            gate = jax.nn.silu(_dot32(y, layer["gate"]["kernel"]) * cfg.mlp_multipliers[0])
            mid = (gate * _dot32(y, layer["up"]["kernel"])).astype(self.dt)
            return h + _dot32(mid, layer["down"]["kernel"]) * cfg.mlp_multipliers[1]

    def _norm_in(self, h, layer):
        with jax.named_scope("norm_in"):
            return _rmsnorm(h, layer["ln_in"]["scale"], self.cfg.rms_norm_eps)

    @staticmethod
    def _a(layer):
        return -jnp.exp(layer["A_log"].astype(jnp.float32))

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

        def layer_fn(h, layer, k_pool, v_pool, conv, ssd, base):
            at = (base // n_blocks) * n_slots + slots
            u = self._norm_in(h, layer)
            with jax.named_scope("ssm"):
                z, raw, step = self._ssm_in(u, layer)
                window = jnp.concatenate([conv[at], raw[:, None, :]], axis=1)
                conv = conv.at[at].set(window[:, 1:])
                xs, b, c = self._conv(window, layer)
                with jax.named_scope("ssd_update"):
                    ssd, y = ssd_decode(ssd, xs, step, self._a(layer), b, c, layer["D"],
                                        at, live, impl=cfg.attn_impl)
                ssm = self._ssm_out(y, z, layer)
            q, k, v = self._qkv(u, layer, positions)
            k_pool, v_pool = write(k_pool, k, base), write(v_pool, v, base)
            with jax.named_scope("gqa_attention"):
                att = gqa_paged_attention(q, k_pool, v_pool, btab + base, positions,
                                          impl=cfg.attn_impl)
            return self._close(h, layer, ssm, att), k_pool, v_pool, conv, ssd

        x, *arrays = _carry_loop(params["blocks"], x, tuple(arrays), layer_fn)
        return x, tuple(arrays)

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

        def layer_fn(h, layer, k_pool, v_pool, conv, ssd, base):
            at = (base // n_blocks) * n_slots + slot
            u = self._norm_in(h, layer)
            with jax.named_scope("ssm"):
                z, raw, step = self._ssm_in(u, layer)
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
                ssm = self._ssm_out(y, z, layer)
            q, k, v = self._qkv(u, layer, positions)
            k_pool, v_pool = write(k_pool, k, base), write(v_pool, v, base)
            with jax.named_scope("chunk_attention"):
                att = gqa_chunk_attention(q, k_pool, v_pool, btab + base, positions,
                                          start + n_valid)
            return self._close(h, layer, ssm, att), k_pool, v_pool, conv, ssd

        x, *arrays = _carry_loop(params["blocks"], x, tuple(arrays), layer_fn)
        return jax.lax.dynamic_slice_in_dim(x, jnp.maximum(n_valid - 1, 0), 1), tuple(arrays)
