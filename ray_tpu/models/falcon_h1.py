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
over the layers with the four pools as its carry.  The Mamba-2 mixer with its
decode and chunk step and the paged K/V step are ``models.blocks``' (shared
with Granite-4.0-H and LFM2); the muP vector, the multipliers and the
parallel block are this family's.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.model_runner import _carry_loop
from ray_tpu.models.blocks import (
    Mamba2, dot32, last_valid, normal_layers, paged_kv_chunk, paged_kv_decode, rmsnorm)
from ray_tpu.ops.gqa_attention import rotary_half


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

    def mixer(self) -> Mamba2:
        """The mixer (``models.blocks``), the muP vector over ``W_in``'s columns."""
        return Mamba2(
            d_ssm=self.d_ssm, heads=self.ssm_heads, d_state=self.d_state, n_groups=self.n_groups,
            d_conv=self.d_conv, eps=self.rms_norm_eps, dtype=jnp.dtype(self.dtype),
            sub=self.ssm_chunk, impl=self.attn_impl, in_scale=self.mup_vector())

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
        return normal_layers(key, layers, shape, std, dt)

    ks = jax.random.split(rng, 14)
    blocks = {
        "ln_in": {"scale": jnp.ones((n, d), dt)},
        "ln_ff": {"scale": jnp.ones((n, d), dt)},
        "q": {"kernel": normal(
            ks[1], (d, hq), cfg.score_spread * d**-0.5 / cfg.attention_in_multiplier)},
        "k": {"kernel": normal(ks[2], (d, hkv), d**-0.5 / (
            cfg.attention_in_multiplier * cfg.key_multiplier))},
        "v": {"kernel": normal(ks[3], (d, hkv), d**-0.5 / cfg.attention_in_multiplier)},
        "o": {"kernel": normal(ks[4], (hq, d), hq**-0.5 / cfg.attention_out_multiplier)},
        **cfg.mixer().init(
            (ks[0], ks[5], ks[6], None, ks[7], ks[8]), n, d,
            d**-0.5 / (cfg.ssm_in_multiplier * cfg.mup_vector()),
            cfg.d_ssm**-0.5 / cfg.ssm_out_multiplier,
            (cfg.a_min, cfg.a_max), (cfg.dt_min, cfg.dt_max)),
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


class FalconH1Body:
    """The family's traced layer programs for ``HybridModelRunner``.  The
    pools ride as ``HybridPool.arrays`` has them: ``(k, v, conv, ssd)``: K and
    V ``(L, blocks, K, block, e)``, the convolution's tails ``(L, slots + 1,
    (d_conv - 1) * conv_dim)``, a slot's ONE row (``blocks.Mamba2``), and the
    SSD states ``(L, slots + 1, H, P, N)``.  A table row is ``[slot, block
    table...]``, slot 0 and block 0 the trash a dead decode row and a padded
    chunk row write."""

    def __init__(self, cfg: FalconH1Config):
        self.cfg = cfg
        self.dt = jnp.dtype(cfg.dtype)
        self.ssm = cfg.mixer()

    # -- what the pools hold ----------------------------------------------

    def kv_layout(self) -> dict:
        """The paged pool: EVERY layer's K and V, a key-value head a head."""
        cfg = self.cfg
        return {"n_layers": cfg.n_layers, "n_heads": cfg.n_kv_heads,
                "head_dim": cfg.head_dim, "dtype": cfg.dtype}

    def state_leaves(self, block_size: int) -> dict:
        """name -> (layers, one slot's shape, dtype) of the state pool."""
        return self.ssm.state_leaves(self.cfg.n_layers, self.cfg.state_dtype)

    # -- shared layer math --------------------------------------------------

    def embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["embed"]["tokens"][tokens].astype(
                jnp.float32) * self.cfg.embedding_multiplier

    def lm_head(self, params, h):
        with jax.named_scope("lm_head"):
            y = rmsnorm(h, params["ln_f"]["scale"], self.cfg.rms_norm_eps).astype(self.dt)
            return dot32(y, params["lm_head"]["kernel"]) * self.cfg.lm_head_multiplier

    def _qkv(self, u, layer, positions):
        """q (n, H, e), k, v (n, K, e) in the compute dtype, q and k rotated."""
        cfg, n = self.cfg, u.shape[0]
        with jax.named_scope("qkv"):
            a = (u * cfg.attention_in_multiplier).astype(self.dt)
            q = dot32(a, layer["q"]["kernel"]).reshape(n, cfg.n_heads, cfg.head_dim)
            k = (dot32(a, layer["k"]["kernel"]) * cfg.key_multiplier).reshape(
                n, cfg.n_kv_heads, cfg.head_dim)
            v = dot32(a, layer["v"]["kernel"]).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            q = rotary_half(q, positions, cfg.rope_theta).astype(self.dt)
            k = rotary_half(k, positions, cfg.rope_theta).astype(self.dt)
            return q, k, v.astype(self.dt)

    def _close(self, h, layer, ssm, att):
        """Both branches onto the stream, then the MLP."""
        cfg = self.cfg
        with jax.named_scope("attn_out"):
            h = h + cfg.ssm_out_multiplier * ssm + cfg.attention_out_multiplier * dot32(
                att.astype(self.dt).reshape(h.shape[0], -1), layer["o"]["kernel"])
        with jax.named_scope("mlp"):
            y = rmsnorm(h, layer["ln_ff"]["scale"], cfg.rms_norm_eps).astype(self.dt)
            gate = jax.nn.silu(dot32(y, layer["gate"]["kernel"]) * cfg.mlp_multipliers[0])
            mid = (gate * dot32(y, layer["up"]["kernel"])).astype(self.dt)
            return h + dot32(mid, layer["down"]["kernel"]) * cfg.mlp_multipliers[1]

    def _norm_in(self, h, layer):
        with jax.named_scope("norm_in"):
            return rmsnorm(h, layer["ln_in"]["scale"], self.cfg.rms_norm_eps)

    def _layers(self, params, x, arrays, positions, slots, ssm, attend):
        """A step's layers, written once for both steps: one ``_carry_loop``
        with the four pools as its carry.  ``ssm(u, layer, conv, ssd, at)`` is
        the step's Mamba-2 step (``self.ssm.decode`` / ``.chunk``), ``attend``
        its paged K/V step, ``slots`` its slot(s) of state."""
        n_blocks, n_slots = arrays[0].shape[1], arrays[2].shape[1]

        def layer_fn(h, layer, k_pool, v_pool, conv, ssd, base):
            at = (base // n_blocks) * n_slots + slots
            u = self._norm_in(h, layer)
            with jax.named_scope("ssm"):
                y, conv, ssd = ssm(u * self.cfg.ssm_in_multiplier, layer, conv, ssd, at)
            q, k, v = self._qkv(u, layer, positions)
            att, k_pool, v_pool = attend(q, k, v, k_pool, v_pool, base)
            return self._close(h, layer, y, att), k_pool, v_pool, conv, ssd

        x, *arrays = _carry_loop(params["blocks"], x, tuple(arrays), layer_fn)
        return x, tuple(arrays)

    def decode(self, params, x, arrays, positions, tables):
        """One token of many sequences.  x: (S, d) embedded tokens at
        ``positions``; tables: (S, 1 + T).  Returns (hidden (S, d), arrays)."""
        slots, btab = tables[:, 0], tables[:, 1:]
        ssm = functools.partial(self.ssm.decode, live=slots > 0)
        attend = paged_kv_decode(arrays[0], btab, positions, self.cfg.attn_impl)
        return self._layers(params, x, arrays, positions, slots, ssm, attend)

    def chunk(self, params, x, arrays, start, n_valid, table):
        """A prefill chunk.  x: (C, d) embedded tokens of ONE sequence at
        ``start ..``, the first ``n_valid`` real; table: (1 + T,).  Returns
        (the last valid token's hidden (1, d), arrays)."""
        slot, btab = table[0], table[1:]
        positions = start + jnp.arange(x.shape[0], dtype=jnp.int32)
        valid, fresh = jnp.arange(x.shape[0]) < n_valid, start == 0
        ssm = functools.partial(self.ssm.chunk, fresh=fresh, n_valid=n_valid, valid=valid)
        attend = paged_kv_chunk(arrays[0], btab, positions, start, n_valid)
        x, arrays = self._layers(params, x, arrays, positions, slot, ssm, attend)
        return last_valid(x, n_valid), arrays
