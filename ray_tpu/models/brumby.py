"""Brumby family decoder (manifestai/Brumby-14B-Base): power retention in
place of attention, so a sequence's past is a fixed-size recurrent state and
no layer has keys or values to cache.

The block, pre-norm and bias-free: ``x += Ret(RMSNorm(x))``, ``x +=
W_down(silu(W_gate h) * W_up h)`` with ``h = RMSNorm(x)``; a final RMSNorm
and an untied head.  ``Ret``: grouped projections (``n_heads`` query heads
over ``n_kv_heads`` key-value heads), a per-head RMSNorm on q and k, rotary
over the whole head (half-split form), one gate per key-value head ``log g =
log_sigmoid(x W_g + gate_shift)``, then degree-2 power retention
(``ops.power_retention``: the equations, the state's layout, the decode
kernel).  ``gate_shift`` is a constant of the configuration, not a
parameter: trained gates sit near 1, and zero-mean random ``W_g`` alone
gives g = 0.5, a memory of two tokens (0.0 is the bare formula).

Everything of the family is HERE: the configuration, the seeded
initializer and the layer body.  Serving takes the body through
``BrumbyConfig.serving_body()`` (``llm.state_runner.StateModelRunner`` names
no family): ``embed``, ``decode_layer``, ``chunk_layer``, ``lm_head`` and the
shape of one sequence's state in one layer.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.models.blocks import rmsnorm
from ray_tpu.ops.power_retention import retention_chunk, retention_decode, state_dims


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    seq_len: int = 32768
    d_model: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 17408
    rms_eps: float = 1e-6
    rope_theta: float = 1e6
    #: degree of the power retention; only 2 is implemented
    power: int = 2
    retention_eps: float = 1e-6
    gate_shift: float = 6.0
    dtype: str = "bfloat16"
    #: the recurrent state's dtype.  float32: a bfloat16 state loses the
    #: rank-one updates once it has outgrown them
    state_dtype: str = "float32"
    retention_impl: str = "auto"

    #: what a sequence holds on the device (``llm.engine``): a fixed-size
    #: state, not K/V blocks
    cache_kind = "state"

    def __post_init__(self):
        if self.power != 2:
            raise NotImplementedError("power retention is implemented for power 2")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("n_heads must be a multiple of n_kv_heads, head_dim even")

    def serving_body(self) -> "BrumbyBody":
        return BrumbyBody(self)


def brumby_init(rng: jax.Array, cfg: BrumbyConfig) -> dict:
    """Seeded random parameters (float32 masters).  Every projection is
    normal at ``fan_in ** -0.5``, so each keeps its input's scale; the
    embedding is normal at 1.  The retention's output averages many values
    and comes out small, the MLP's does not: ``attn_out`` has a gain of 4
    and ``mlp_down`` of 0.5, so that both branches move the stream."""
    d, dff, L, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab_size
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 10)

    def kernel(key, shape, fan_in, gain=1.0):
        return jax.random.normal(key, shape, jnp.float32) * (gain * fan_in**-0.5)

    blocks = {
        "ln1": {"scale": jnp.ones((L, d))},
        "q": {"kernel": kernel(ks[0], (L, d, hq * hd), d)},
        "k": {"kernel": kernel(ks[1], (L, d, hkv * hd), d)},
        "v": {"kernel": kernel(ks[2], (L, d, hkv * hd), d)},
        "gate": {"kernel": kernel(ks[3], (L, d, hkv), d)},
        "q_norm": {"scale": jnp.ones((L, hd))},
        "k_norm": {"scale": jnp.ones((L, hd))},
        "attn_out": {"kernel": kernel(ks[4], (L, hq * hd, d), hq * hd, 4.0)},
        "ln2": {"scale": jnp.ones((L, d))},
        "mlp_gate": {"kernel": kernel(ks[5], (L, d, dff), d)},
        "mlp_up": {"kernel": kernel(ks[6], (L, d, dff), d)},
        "mlp_down": {"kernel": kernel(ks[7], (L, dff, d), dff, 0.5)},
    }
    return {
        "embed": {"tokens": jax.random.normal(ks[8], (V, d), jnp.float32)},
        "blocks": blocks,
        "ln_f": {"scale": jnp.ones((d,))},
        "lm_head": {"kernel": kernel(ks[9], (d, V), d)},
    }


def _rotary_half(x, positions, theta):
    """Half-split rotary over the whole head, per-row positions.  x: (n,
    heads, hd); positions: (n,) int32."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None, None] * inv_freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


class BrumbyBody:
    """The family's traced layer math for the state runner.  ``state`` is
    the whole pool as ``model_runner._carry_loop`` carries it, ``(layers *
    slots, kv heads, VD, F)``, and ``base`` this layer's first slot there."""

    def __init__(self, cfg: BrumbyConfig):
        self.cfg = cfg
        self.state_shape = (cfg.n_kv_heads,) + state_dims(cfg.head_dim)
        self.state_dtype = cfg.state_dtype

    def _norm(self, x, scale):
        """RMSNorm, handed on in x's dtype."""
        return rmsnorm(x, scale, self.cfg.rms_eps).astype(x.dtype)

    def embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["embed"]["tokens"][tokens].astype(jnp.dtype(self.cfg.dtype))

    def lm_head(self, params, h):
        with jax.named_scope("lm_head"):
            h = self._norm(h, params["ln_f"]["scale"])
            return jnp.dot(h, params["lm_head"]["kernel"].astype(h.dtype),
                           preferred_element_type=jnp.float32)

    def _qkvg(self, layer, h, positions):
        """h: (n, d) normed hidden -> q (n, Hq, hd), k, v (n, Hkv, hd) in
        h's dtype, log_g (n, Hkv) float32."""
        cfg, dt, n = self.cfg, h.dtype, h.shape[0]
        with jax.named_scope("qkv"):
            q = (h @ layer["q"]["kernel"].astype(dt)).reshape(n, cfg.n_heads, cfg.head_dim)
            k = (h @ layer["k"]["kernel"].astype(dt)).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            v = (h @ layer["v"]["kernel"].astype(dt)).reshape(n, cfg.n_kv_heads, cfg.head_dim)
            z = jnp.dot(h, layer["gate"]["kernel"].astype(dt),
                        preferred_element_type=jnp.float32)
            log_g = jax.nn.log_sigmoid(z + cfg.gate_shift)
        with jax.named_scope("qk_norm"):
            q = self._norm(q, layer["q_norm"]["scale"])
            k = self._norm(k, layer["k_norm"]["scale"])
            q = _rotary_half(q, positions, cfg.rope_theta)
            k = _rotary_half(k, positions, cfg.rope_theta)
        return q, k, v, log_g

    def _finish(self, x, layer, att):
        """The residual adds after the retention: its output projection,
        then the SwiGLU MLP on the second norm."""
        dt = x.dtype
        with jax.named_scope("attn_out"):
            x = x + att.astype(dt).reshape(x.shape[0], -1) @ layer["attn_out"]["kernel"].astype(dt)
        with jax.named_scope("mlp"):
            h = self._norm(x, layer["ln2"]["scale"])
            mid = jax.nn.silu(h @ layer["mlp_gate"]["kernel"].astype(dt)) * (
                h @ layer["mlp_up"]["kernel"].astype(dt))
            return x + mid @ layer["mlp_down"]["kernel"].astype(dt)

    def decode_layer(self, x, layer, state, base, positions, slots, live):
        """One layer of a decode batch: row i updates and reads the state
        at ``base + slots[i]`` where ``live[i]``; a dead row touches none."""
        cfg = self.cfg
        h = self._norm(x, layer["ln1"]["scale"])
        q, k, v, log_g = self._qkvg(layer, h, positions)
        with jax.named_scope("retention"):
            state, att = retention_decode(
                state, q, k, v, log_g, base + slots, live,
                eps=cfg.retention_eps, impl=cfg.retention_impl)
        return self._finish(x, layer, att), state

    def chunk_layer(self, x, layer, state, base, positions, slot, valid):
        """One layer of one sequence's prefill chunk on the state at ``base
        + slot``.  A chunk at position 0 OVERWRITES what the slot's last
        owner left; a later one reads the state, and both write it back."""
        cfg = self.cfg
        h = self._norm(x, layer["ln1"]["scale"])
        q, k, v, log_g = self._qkvg(layer, h, positions)
        with jax.named_scope("retention_chunk"):
            at = base + slot
            old = jax.lax.dynamic_index_in_dim(state, at, 0, keepdims=False)
            s0 = jnp.where(positions[0] > 0, old.astype(jnp.float32), 0.0)
            att, s1 = retention_chunk(s0, q, k, v, log_g, valid, eps=cfg.retention_eps)
            state = jax.lax.dynamic_update_index_in_dim(
                state, s1.astype(state.dtype), at, 0)
        return self._finish(x, layer, att), state
