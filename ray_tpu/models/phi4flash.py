"""Phi-4-mini-flash-reasoning (microsoft/Phi-4-mini-flash-reasoning): a
decoder-hybrid-decoder (SambaY, arXiv:2507.06607).  Five kinds of layer in
ONE model, no positional encoding anywhere:

* layers ``l < L/2``: even ``l`` a **state-space** layer (Mamba-1), odd
  ``l`` **window attention** over the last ``W`` tokens;
* layer ``L/2``: state-space, whose scan output ``m`` is the **memory**;
* layer ``L/2 + 1``: **full attention**, whose keys and values are the
  **shared K/V**;
* layers above: even ``l`` a **memory gate** (GMU: ``(m * silu(x W_1))
  W_2``, ``m`` the memory AT THE SAME TOKEN, no cache at all), odd ``l``
  **cross attention**: its own queries over layer ``L/2 + 1``'s K/V.

Block ``l``: ``h = x + Mixer_l(LN(x))``, ``y = h + W_down(silu(g) * u)``
with ``[g, u] = LN'(h) W_gate_up``; LayerNorm with scale and bias; a final
LayerNorm; logits through the tied embedding.  Attention is differential
(``ops.diff_attention``): heads in pairs, ``lambda = exp(lq1 . lk1) -
exp(lq2 . lk2) + lambda_0(l)``, ``lambda_0(l) = 0.8 - 0.6 exp(-0.3 l)``.
The state-space mixer is ``ops.selective_scan`` behind a causal depthwise
convolution of width 4.

What a sequence holds on the device (``llm.cache.HybridPool``): blocks of
the ONE full-attention layer's K/V, which grow with it, and a slot of
fixed-size state: per state-space layer the scan state ``(N, D)`` float32
and the convolution's last 3 inputs, per window layer a ring of ``W``
tokens of K and V written at ``position mod W``.  A ring is ``W / block``
blocks of the paged K/V shape with a fixed table a slot, so window, full
and cross attention are all ``diff_paged_attention``; without positional
encoding a ring's order does not matter, only which entries are live.

Everything of the family is HERE: the configuration, the seeded
initializer and the layer programs ``llm.state_runner.HybridModelRunner``
(which names no family) takes through ``serving_body()``: two
``_carry_loop`` segments of period 2 around the two middle layers, the
memory and the shared K/V passing from the first half to the second
inside the program.  A prefill chunk runs the second half for ONE token,
the chunk's last valid one: nothing above layer ``L/2 + 1`` is cached, so
the other tokens' upper halves are never read (the paper's linear prefill).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.llm.model_runner import _carry_loop, _chunk_write, _slots_write
from ray_tpu.models.blocks import dot32 as _dot32
from ray_tpu.ops.diff_attention import (
    diff_combine,
    diff_dense_attention,
    diff_paged_attention,
    pair_heads,
)
from ray_tpu.ops.selective_scan import scan_chunk, scan_decode

@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    seq_len: int = 262144
    d_model: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    d_ff: int = 10240
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    #: the state-space mixer (Mamba-1): inner width, state size,
    #: convolution width, rank of the step-size projection and the range
    #: its bias is initialised to
    d_inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    dt_min: float = 1e-3
    dt_max: float = 0.1
    subln_eps: float = 1e-5
    init_range: float = 0.02
    dtype: str = "bfloat16"
    #: the scan state's dtype.  float32: a bfloat16 state loses the small
    #: steps (delta down to 1e-3) of a state it has integrated
    state_dtype: str = "float32"
    attn_impl: str = "auto"

    #: what a sequence holds on the device (``llm.engine``): blocks of one
    #: layer's K/V AND a slot of fixed-size state
    cache_kind = "hybrid"

    def __post_init__(self):
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError("n_layers must be a multiple of 4, at least 8")
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError("heads come in pairs, query heads in whole groups")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def mid(self) -> int:
        """The memory layer; ``mid + 1`` is the full-attention layer."""
        return self.n_layers // 2

    def layer_kinds(self) -> list:
        mid = self.mid
        return [
            "ssm" if i == mid else "full" if i == mid + 1
            else ("ssm", "window")[i % 2] if i < mid else ("gmu", "cross")[i % 2]
            for i in range(self.n_layers)
        ]

    def serving_body(self) -> "Phi4FlashBody":
        return Phi4FlashBody(self)


def phi4flash_init(rng: jax.Array, cfg: Phi4FlashConfig) -> dict:
    """Seeded random parameters (float32 masters).  Projections normal at
    ``fan_in ** -0.5``; the tied embedding normal at ``init_range`` (0.02:
    logits of size about 1 through a 2560-wide tied head); LayerNorm scales
    1, biases 0.  The state-space mixer as Mamba publishes it: ``A_log =
    log(1..N)``, ``D_skip = 1``, the step's bias the inverse softplus of a
    log-uniform draw from ``[dt_min, dt_max]``, its projection uniform at
    ``dt_rank ** -0.5``, the convolution uniform at ``d_conv ** -0.5``.
    The lambda vectors normal at 0.1.  No gain anywhere: every mixer ends
    in a projection of a unit-sized input (the attention's own RMSNorm
    sees to that), so each moves the stream by about as much as the MLP."""
    d, dff, e = cfg.d_model, cfg.d_ff, cfg.head_dim
    D, N, R = cfg.d_inner, cfg.d_state, cfg.dt_rank
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    mid = cfg.mid
    n1, n2 = mid // 2, (cfg.n_layers - mid - 2) // 2

    def normal(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * fan_in**-0.5

    def block(key, n: tuple) -> dict:
        ks = jax.random.split(key, 2)
        return {
            "ln1": {"scale": jnp.ones(n + (d,)), "bias": jnp.zeros(n + (d,))},
            "ln2": {"scale": jnp.ones(n + (d,)), "bias": jnp.zeros(n + (d,))},
            "mlp_gate_up": {"kernel": normal(ks[0], n + (d, 2 * dff), d)},
            "mlp_down": {"kernel": normal(ks[1], n + (dff, d), dff)},
        }

    def ssm(key, n: tuple) -> dict:
        ks = jax.random.split(key, 7)
        dt = jnp.exp(jax.random.uniform(ks[0], n + (D,)) * (
            math.log(cfg.dt_max) - math.log(cfg.dt_min)) + math.log(cfg.dt_min))
        uniform = lambda k, shape, fan: jax.random.uniform(  # noqa: E731
            k, shape, jnp.float32, -1.0, 1.0) * fan**-0.5
        return dict(block(ks[1], n), **{
            "in": {"kernel": normal(ks[2], n + (d, 2 * D), d)},
            "conv": {"kernel": uniform(ks[3], n + (cfg.d_conv, D), cfg.d_conv),
                     "bias": jnp.zeros(n + (D,))},
            "x": {"kernel": normal(ks[4], n + (D, R + 2 * N), D)},
            "dt": {"kernel": uniform(ks[5], n + (R, D), R),
                   "bias": dt + jnp.log(-jnp.expm1(-dt))},
            # (N, D): the state's layout (ops.selective_scan)
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None], n + (N, D)),
            "D_skip": jnp.ones(n + (D,)),
            "out": {"kernel": normal(ks[6], n + (D, d), D)},
        })

    def attn(key, n: tuple, cross: bool) -> dict:
        ks = jax.random.split(key, 7)
        width = hq * e if cross else (hq + 2 * hkv) * e
        return dict(block(ks[0], n), **{
            "q" if cross else "qkv": {
                "kernel": normal(ks[1], n + (d, width), d),
                "bias": jnp.zeros(n + (width,))},
            "lam": {name: 0.1 * jax.random.normal(k, n + (e,), jnp.float32)
                    for name, k in zip(("q1", "k1", "q2", "k2"), ks[2:6])},
            "subln": {"scale": jnp.ones(n + (2 * e,))},
            "o": {"kernel": normal(ks[6], n + (hq * e, d), hq * e),
                  "bias": jnp.zeros(n + (d,))},
        })

    def gmu(key, n: tuple) -> dict:
        ks = jax.random.split(key, 3)
        return dict(block(ks[0], n), **{
            "in": {"kernel": normal(ks[1], n + (d, D), d)},
            "out": {"kernel": normal(ks[2], n + (D, d), D)},
        })

    ks = jax.random.split(rng, 7)
    return {
        "embed": {"tokens": cfg.init_range * jax.random.normal(
            ks[0], (cfg.vocab_size, d), jnp.float32)},
        "seg1": {"ssm": ssm(ks[1], (n1,)), "window": attn(ks[2], (n1,), False)},
        "memory": ssm(ks[3], ()),
        "full": attn(ks[4], (), False),
        "seg2": {"gmu": gmu(ks[5], (n2,)), "cross": attn(ks[6], (n2,), True)},
        "ln_f": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
    }


def _layernorm(x, ln, eps, dtype):
    """LayerNorm of the float32 stream, handed on in the compute dtype."""
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * ln["scale"].astype(jnp.float32)
            + ln["bias"].astype(jnp.float32)).astype(dtype)


class Phi4FlashBody:
    """The family's traced layer programs for the hybrid runner.  The
    pools ride as ``HybridPool.arrays`` has them: ``(k, v, conv, scan,
    ring_k, ring_v)``; a table row is ``[slot, block table...]``, slot 0
    and block 0 the trash a dead decode row and a padded chunk row write."""

    def __init__(self, cfg: Phi4FlashConfig):
        self.cfg = cfg
        #: what the matrix products take.  The residual stream itself is
        #: float32 (a row is 10 KB): 64 roundings of a growing sum would be
        #: most of the distance to the reference, and cost nothing to spare
        self.dt = jnp.dtype(cfg.dtype)
        self.pairs = cfg.n_kv_heads // 2
        self.pair_dim = 2 * cfg.head_dim
        self.n_ssm = cfg.mid // 2 + 1
        self.n_window = cfg.mid // 2

    # -- what the pools hold ----------------------------------------------

    def kv_layout(self) -> dict:
        """The paged pool: ONE layer, a key-value pair a head."""
        return {"n_layers": 1, "n_heads": self.pairs, "head_dim": self.pair_dim,
                "dtype": self.cfg.dtype}

    def state_leaves(self, block_size: int) -> dict:
        """name -> (layers, one slot's shape, dtype) of the state pool."""
        cfg = self.cfg
        if cfg.sliding_window % block_size:
            raise ValueError("sliding_window must be whole blocks")
        ring = (cfg.sliding_window // block_size, self.pairs, block_size, self.pair_dim)
        return {
            "conv": (self.n_ssm, (cfg.d_conv - 1, cfg.d_inner), cfg.dtype),
            "scan": (self.n_ssm, (cfg.d_state, cfg.d_inner), cfg.state_dtype),
            "ring_k": (self.n_window, ring, cfg.dtype),
            "ring_v": (self.n_window, ring, cfg.dtype),
        }

    # -- shared layer math --------------------------------------------------

    def embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["embed"]["tokens"][tokens].astype(jnp.float32)

    def lm_head(self, params, h):
        with jax.named_scope("lm_head"):
            h = _layernorm(h, params["ln_f"], self.cfg.layer_norm_eps, self.dt)
            return jax.lax.dot_general(
                h, params["embed"]["tokens"].astype(h.dtype),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    def _mlp(self, h, layer):
        cfg, dt = self.cfg, self.dt
        with jax.named_scope("mlp"):
            y = _layernorm(h, layer["ln2"], cfg.layer_norm_eps, dt)
            gu = y @ layer["mlp_gate_up"]["kernel"].astype(dt)
            mid = jax.nn.silu(gu[:, :cfg.d_ff]) * gu[:, cfg.d_ff:]
            return h + _dot32(mid, layer["mlp_down"]["kernel"])

    def _ssm_in(self, x, layer):
        """LN and the input projection: (u before the convolution, z)."""
        with jax.named_scope("ssm_proj"):
            y = _layernorm(x, layer["ln1"], self.cfg.layer_norm_eps, self.dt)
            uz = y @ layer["in"]["kernel"].astype(self.dt)
            return uz[:, :self.cfg.d_inner], uz[:, self.cfg.d_inner:]

    def _ssm_terms(self, u32, layer):
        """From the convolved input (float32): (u, delta, A, B, C)."""
        cfg, dt = self.cfg, self.dt
        u = jax.nn.silu(u32)
        rbc = _dot32(u.astype(dt), layer["x"]["kernel"])
        r, b, c = jnp.split(rbc, [cfg.dt_rank, cfg.dt_rank + cfg.d_state], axis=-1)
        delta = jax.nn.softplus(
            _dot32(r.astype(dt), layer["dt"]["kernel"])
            + layer["dt"]["bias"].astype(jnp.float32))
        return u, delta, -jnp.exp(layer["A_log"].astype(jnp.float32)), b, c

    def _ssm_out(self, x, layer, m, z):
        """The gate, the output projection, the residual and the MLP."""
        with jax.named_scope("ssm"):
            gated = (m * jax.nn.silu(z.astype(jnp.float32))).astype(self.dt)
        with jax.named_scope("ssm_proj"):
            x = x + _dot32(gated, layer["out"]["kernel"])
        return self._mlp(x, layer)

    def _lambda(self, vectors, lam0):
        """``exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_0``, float32."""
        w = {k: v.astype(jnp.float32) for k, v in vectors.items()}
        return lam0 + jnp.exp((w["q1"] * w["k1"]).sum()) - jnp.exp((w["q2"] * w["k2"]).sum())

    def _attn_out(self, x, layer, a1, a2, index):
        """The differential combination, the output projection, the
        residual and the MLP.  ``index``: the layer's, traced in a loop."""
        cfg = self.cfg
        with jax.named_scope("attn_out"):
            lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(index, jnp.float32))
            att = diff_combine(a1, a2, self._lambda(layer["lam"], lam0), lam0,
                               layer["subln"]["scale"], cfg.subln_eps)
            att = att.astype(self.dt).reshape(x.shape[0], -1)
            x = x + _dot32(att, layer["o"]["kernel"]) + layer["o"]["bias"].astype(jnp.float32)
        return self._mlp(x, layer)

    def _qkv(self, x, layer):
        """LN and the projections: q (n, H, e); k, v (n, K/2, 2e) pairs."""
        cfg, n = self.cfg, x.shape[0]
        with jax.named_scope("qkv"):
            y = _layernorm(x, layer["ln1"], cfg.layer_norm_eps, self.dt)
            qkv = y @ layer["qkv"]["kernel"].astype(self.dt) + layer["qkv"][
                "bias"].astype(self.dt)
            nq, nkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            q = qkv[:, :nq].reshape(n, cfg.n_heads, cfg.head_dim)
            k = pair_heads(qkv[:, nq:nq + nkv], cfg.n_kv_heads)
            v = pair_heads(qkv[:, nq + nkv:], cfg.n_kv_heads)
        return q, k, v

    def _shared_attention(self, q, k_pool, v_pool, btab, positions):
        """One query a row over the shared K/V up to its own position."""
        with jax.named_scope("shared_kv_attention"):
            return diff_paged_attention(
                q, k_pool, v_pool, btab, positions, self.cfg.n_kv_heads,
                impl=self.cfg.attn_impl)

    def _upper(self, params, x, memory, k_pool, v_pool, btab, positions):
        """The second half: (GMU, cross) pairs over rows that carry their
        ``memory`` and attend the shared K/V up to ``positions``."""
        cfg = self.cfg

        def pair(x, layers, index):
            gmu, cross = layers["gmu"], layers["cross"]
            with jax.named_scope("gmu"):
                y = _layernorm(x, gmu["ln1"], cfg.layer_norm_eps, self.dt)
                gated = (memory * jax.nn.silu(_dot32(y, gmu["in"]["kernel"]))).astype(self.dt)
                x = x + _dot32(gated, gmu["out"]["kernel"])
            x = self._mlp(x, gmu)
            with jax.named_scope("qkv"):
                y = _layernorm(x, cross["ln1"], cfg.layer_norm_eps, self.dt)
                q = y @ cross["q"]["kernel"].astype(self.dt) + cross["q"]["bias"].astype(self.dt)
            a1, a2 = self._shared_attention(
                q.reshape(x.shape[0], cfg.n_heads, cfg.head_dim), k_pool, v_pool, btab,
                positions)
            return (self._attn_out(x, cross, a1, a2, cfg.mid + 3 + 2 * index),)

        return _carry_loop(params["seg2"], x, (), pair)[0]

    def _ring_entry(self, position):
        """Where a position's key and value stand in their ring."""
        return position % self.cfg.sliding_window

    def _ring_seen(self, positions, held):
        """(C, W) bool: which ring entries, holding the positions ``held``
        (negative: none yet), a chunk's queries at ``positions`` attend."""
        behind = positions[:, None] - held[None, :]
        return (held[None, :] >= 0) & (behind < self.cfg.sliding_window)

    def _views(self, arrays):
        """The pools as the layer programs index them: the one K/V layer
        without its layer axis; the rings as pools of blocks (layers, slots
        * ring blocks, K/2, block, 2e)."""
        k_pool, v_pool, conv, scan, ring_k, ring_v = arrays
        ring = (ring_k.shape[0], -1) + ring_k.shape[3:]
        return (k_pool[0], v_pool[0], conv, scan,
                ring_k.reshape(ring), ring_v.reshape(ring))

    @staticmethod
    def _arrays(views, arrays):
        views = (views[0][None], views[1][None]) + tuple(views[2:])
        return tuple(v.reshape(a.shape) for v, a in zip(views, arrays))

    def _memory_layer(self, step, params, x, conv, scan, slot):
        """Layer ``mid`` on the flat views of the state pools, its state
        the last of each slot's.  ``step``: ``_ssm_decode`` / ``_ssm_chunk``
        bound to what it needs.  Returns (x, memory, conv, scan)."""
        n_slots = conv.shape[1]
        flat = lambda p: p.reshape((-1,) + p.shape[2:])  # noqa: E731
        at = (self.n_ssm - 1) * n_slots + slot
        out, memory, conv_f, scan_f = step(x, params["memory"], flat(conv), flat(scan), at)
        return out, memory, conv_f.reshape(conv.shape), scan_f.reshape(scan.shape)

    # -- decode: one token of many sequences ---------------------------------

    def _ssm_decode(self, x, layer, conv, scan, at):
        """State-space layer on rows whose states sit at ``at`` of the
        flat views.  Returns (x, memory m float32, conv, scan)."""
        u_raw, z = self._ssm_in(x, layer)
        with jax.named_scope("ssm"):
            window = jnp.concatenate([conv[at], u_raw[:, None, :]], axis=1)  # (S, 4, D)
            conv = conv.at[at].set(window[:, 1:])
            u32 = (window.astype(jnp.float32)
                   * layer["conv"]["kernel"].astype(jnp.float32)).sum(axis=1) + layer[
                       "conv"]["bias"].astype(jnp.float32)
            s, m = scan_decode(scan[at].astype(jnp.float32), *self._ssm_terms(u32, layer),
                               layer["D_skip"].astype(jnp.float32))
            scan = scan.at[at].set(s.astype(scan.dtype))
        return self._ssm_out(x, layer, m, z), m, conv, scan

    def decode(self, params, x, arrays, positions, tables):
        """x: (S, d) embedded tokens at ``positions``; tables: (S, 1 + T).
        Returns (hidden (S, d), arrays)."""
        cfg = self.cfg
        k_pool, v_pool, conv, scan, ring_k, ring_v = self._views(arrays)
        slots, btab = tables[:, 0], tables[:, 1:]
        bs, W, n_slots = k_pool.shape[2], cfg.sliding_window, conv.shape[1]
        rb = W // bs
        # a row's token goes to entry ``position mod W`` of its slot's ring;
        # entries up to ``min(position, W - 1)`` are live, in any order
        entry = self._ring_entry(positions)
        ring_write = _slots_write(slots * rb + entry // bs, entry % bs, bs)
        ring_tab = slots[:, None] * rb + jnp.arange(rb, dtype=jnp.int32)[None, :]
        ring_reach = jnp.minimum(positions, W - 1)

        def pair(x, layers, conv, scan, rk, rv, base):
            x, _, conv, scan = self._ssm_decode(x, layers["ssm"], conv, scan, base + slots)
            win = layers["window"]
            q, k, v = self._qkv(x, win)
            rk = ring_write(rk, k.astype(rk.dtype), base * rb)
            rv = ring_write(rv, v.astype(rv.dtype), base * rb)
            with jax.named_scope("window_attention"):
                a1, a2 = diff_paged_attention(
                    q, rk, rv, ring_tab + base * rb, ring_reach, cfg.n_kv_heads,
                    impl=cfg.attn_impl)
            x = self._attn_out(x, win, a1, a2, 1 + 2 * (base // n_slots))
            return x, conv, scan, rk, rv

        x, conv, scan, ring_k, ring_v = _carry_loop(
            params["seg1"], x, (conv, scan, ring_k, ring_v), pair)
        x, memory, conv, scan = self._memory_layer(
            self._ssm_decode, params, x, conv, scan, slots)

        full = params["full"]
        q, k, v = self._qkv(x, full)
        phys = jnp.take_along_axis(btab, (positions // bs)[:, None], axis=1)[:, 0]
        write = _slots_write(phys, positions % bs, bs)
        k_pool = write(k_pool, k.astype(k_pool.dtype), 0)
        v_pool = write(v_pool, v.astype(v_pool.dtype), 0)
        a1, a2 = self._shared_attention(q, k_pool, v_pool, btab, positions)
        x = self._attn_out(x, full, a1, a2, cfg.mid + 1)
        x = self._upper(params, x, memory, k_pool, v_pool, btab, positions)
        return x, self._arrays((k_pool, v_pool, conv, scan, ring_k, ring_v), arrays)

    # -- prefill: a chunk of one sequence -------------------------------------

    def _ssm_chunk(self, x, layer, conv, scan, at, *, fresh, valid, n_valid):
        """State-space layer on a chunk of the sequence whose state sits at
        ``at``; ``fresh`` (the chunk starts the sequence) overwrites what
        the slot's last owner left.  Returns (x, m, conv, scan)."""
        cfg = self.cfg
        taps, c = cfg.d_conv - 1, x.shape[0]
        u_raw, z = self._ssm_in(x, layer)
        with jax.named_scope("ssm"):
            tail = jnp.where(fresh, 0, jax.lax.dynamic_index_in_dim(conv, at, 0, False))
            seq = jnp.concatenate([tail, u_raw], axis=0)            # (taps + C, D)
            # the last ``taps`` valid inputs are what the next token needs
            conv = jax.lax.dynamic_update_index_in_dim(
                conv, jax.lax.dynamic_slice_in_dim(seq, n_valid, taps), at, 0)
            seq32, kern = seq.astype(jnp.float32), layer["conv"]["kernel"].astype(jnp.float32)
            u32 = sum(seq32[i:i + c] * kern[i] for i in range(cfg.d_conv)) + layer[
                "conv"]["bias"].astype(jnp.float32)
            s0 = jnp.where(fresh, 0.0, jax.lax.dynamic_index_in_dim(
                scan, at, 0, False).astype(jnp.float32))
            m, s1 = scan_chunk(s0, *self._ssm_terms(u32, layer),
                               layer["D_skip"].astype(jnp.float32), valid)
            scan = jax.lax.dynamic_update_index_in_dim(scan, s1.astype(scan.dtype), at, 0)
        return self._ssm_out(x, layer, m, z), m, conv, scan

    def chunk(self, params, x, arrays, start, n_valid, table):
        """x: (C, d) embedded tokens of ONE sequence at ``start ..``, the
        first ``n_valid`` real; table: (1 + T,).  Returns (the last valid
        token's hidden (1, d), arrays)."""
        cfg = self.cfg
        k_pool, v_pool, conv, scan, ring_k, ring_v = self._views(arrays)
        slot, btab = table[0], table[1:]
        C, bs, W, n_slots = x.shape[0], k_pool.shape[2], cfg.sliding_window, conv.shape[1]
        if C > W:
            raise ValueError(f"prefill_chunk {C} exceeds the window {W}")
        rb = W // bs
        positions = start + jnp.arange(C, dtype=jnp.int32)
        ssm_chunk = functools.partial(
            self._ssm_chunk, fresh=start == 0, valid=jnp.arange(C) < n_valid, n_valid=n_valid)
        # ring entry j holds the newest position before the chunk that is j
        # mod W (none: negative).  The chunk's own keys stand beside the
        # ring's in the attention and enter the ring after it
        j = jnp.arange(W, dtype=jnp.int32)
        held = start - 1 - (start - 1 - j) % W
        mask = jnp.concatenate(
            [self._ring_seen(positions, held),
             positions[:, None] >= positions[None, :]], axis=1)         # (C, W + C)
        ring_tab = slot * rb + jnp.arange(rb, dtype=jnp.int32)
        # the blocks a chunk touches may run past the ring's end and on at
        # its beginning: the table goes round once more
        ring_write = _chunk_write(
            jnp.concatenate([ring_tab, ring_tab[:C // bs + 1]]), self._ring_entry(start),
            n_valid, C, bs)

        def tokens_of(pool, ids):
            """Blocks ``ids`` token by token: (len(ids) * block, K/2, 2e)."""
            blocks = pool[ids].transpose(0, 2, 1, 3)
            return blocks.reshape(-1, self.pairs, self.pair_dim)

        def pair(x, layers, conv, scan, rk, rv, base):
            x, _, conv, scan = ssm_chunk(x, layers["ssm"], conv, scan, base + slot)
            win = layers["window"]
            q, k, v = self._qkv(x, win)
            with jax.named_scope("window_attention"):
                ids = ring_tab + base * rb
                a1, a2 = diff_dense_attention(
                    q, jnp.concatenate([tokens_of(rk, ids), k.astype(rk.dtype)]),
                    jnp.concatenate([tokens_of(rv, ids), v.astype(rv.dtype)]), mask)
            rk = ring_write(rk, k.astype(rk.dtype), base * rb)
            rv = ring_write(rv, v.astype(rv.dtype), base * rb)
            x = self._attn_out(x, win, a1, a2, 1 + 2 * (base // n_slots))
            return x, conv, scan, rk, rv

        x, conv, scan, ring_k, ring_v = _carry_loop(
            params["seg1"], x, (conv, scan, ring_k, ring_v), pair)
        x, memory, conv, scan = self._memory_layer(ssm_chunk, params, x, conv, scan, slot)

        full = params["full"]
        q, k, v = self._qkv(x, full)
        write = _chunk_write(btab, start, n_valid, C, bs)
        k_pool = write(k_pool, k.astype(k_pool.dtype), 0)
        v_pool = write(v_pool, v.astype(v_pool.dtype), 0)
        with jax.named_scope("shared_kv_attention"):
            reach = jnp.arange(btab.shape[0] * bs)[None, :] <= positions[:, None]
            a1, a2 = diff_dense_attention(
                q, tokens_of(k_pool, btab), tokens_of(v_pool, btab), reach)
        x = self._attn_out(x, full, a1, a2, cfg.mid + 1)

        # the second half, for the one token whose logits can be asked for
        row = jnp.maximum(n_valid - 1, 0)
        x = jax.lax.dynamic_slice_in_dim(x, row, 1)
        memory = jax.lax.dynamic_slice_in_dim(memory, row, 1)
        x = self._upper(params, x, memory, k_pool, v_pool, btab[None, :], (start + row)[None])
        return x, self._arrays((k_pool, v_pool, conv, scan, ring_k, ring_v), arrays)
