"""The layers the served families share: traced ``jax.numpy``, written ONCE.

A family's file (``models/falcon_h1.py``, ``granite_h.py``, ``lfm2.py``, ...)
holds its widths, its parameter tree, its projections and its multipliers;
what several families compute alike lies here and names none of them.  What
differs between two families arrives as an array, a number or a callable,
never as a flag:

* ``rmsnorm`` and ``dot32``;
* ``Mamba2``: the Mamba-2 mixer (``ops.ssd``) from its input projection to
  its output projection, and the two STEPS around the kernels: a decode's
  (a window of the convolution's tail and one new input, ``ssd_decode``) and
  a chunk's (a fresh slot's zero tail, the tail's and the state's write-back,
  ``ssd_chunk``).  A family opens the scope ``ssm`` around its call and
  applies its own multipliers there; ``ssd_update`` and ``ssd_chunk`` are
  opened here (the benchmark's readers match all three as path segments);
* ``paged_kv_decode`` / ``paged_kv_chunk``: the pool part of a grouped-query
  attention layer over ``llm.cache``'s paged K and V: write the step's K and V
  at the layer's base, then attend (``ops.gqa_attention``, under the scopes
  ``gqa_attention`` and ``chunk_attention``).  The projections before and
  after are the family's;
* ``normal_layers`` and ``gated_mlp_init``: what the seeded initializers
  draw alike (the SAME draws as each family's own copy made);
* ``pattern_layers``: the layer loop of a body whose layers come in RUNS of
  one (mixer, closing) kind, the closing ABSENT (None) where a layer is a
  mixer alone: one ``_carry_loop`` a run over ALL the pools and the routed
  layer's ledger (``ops.moe``), the running index of each kind, the step's
  count at the end.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import math

import jax
import jax.numpy as jnp

from ray_tpu.llm.model_runner import _carry_loop, _chunk_write, _slots_write
from ray_tpu.ops.gqa_attention import gqa_chunk_attention, gqa_paged_attention
from ray_tpu.ops.moe import count_step
from ray_tpu.ops.ssd import ssd_chunk, ssd_decode


def rmsnorm(x, scale, eps):
    """RMSNorm in float32 over the last axis, float32 out."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def dot32(x, kernel):
    """x @ kernel on x's dtype, float32 out."""
    return jnp.dot(x, kernel.astype(x.dtype), preferred_element_type=jnp.float32)


def last_valid(x, n_valid):
    """The last valid row of a chunk's hidden states: (1, d)."""
    return jax.lax.dynamic_slice_in_dim(x, jnp.maximum(n_valid - 1, 0), 1)


def normal_layers(key, n: int, shape: tuple, std, dtype):
    """Seeded normal weights ``(n,) + shape`` in ``dtype``, made one layer (an
    expert) at a time: float32 masters of a whole stack at the published
    sizes would not fit.  ``std``: a number, or a vector over the last axis."""
    std = jnp.asarray(std, jnp.float32)
    return jax.lax.map(
        lambda k: (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype),
        jax.random.split(key, n))


def gated_mlp_init(key, n: int, d: int, width: int, make, out_gain: float = 1.0) -> dict:
    """``n`` gated MLPs ``d -> width -> d`` for ``ops.moe.swiglu``, each matrix
    from ``make(key, n, shape, std)`` at ``fan_in ** -0.5`` (``down`` times
    ``out_gain``)."""
    ks = jax.random.split(key, 3)
    return {"gate": make(ks[0], n, (d, width), d**-0.5),
            "up": make(ks[1], n, (d, width), d**-0.5),
            "down": make(ks[2], n, (width, d), width**-0.5 * out_gain)}


@dataclasses.dataclass(frozen=True, eq=False)
class Mamba2:
    """The Mamba-2 mixer of one layer, ``layer`` holding ``ssm_in``, ``conv``
    (kernel and bias), ``dt_bias``, ``A_log``, ``D``, ``ssm_norm`` and
    ``ssm_out``::

        [z d_ssm | xBC d_ssm + 2 G N | dt H] = (u W_in) * in_scale
        [x | B | C] = silu(causal depthwise convolution of xBC, d_conv taps, + bias)
        dt = softplus(dt + dt_bias),  A = -exp(A_log),  the recurrence of ops.ssd
        out = RMSNorm within each of G groups (y . silu(z)) W_out   (gate, THEN norm)

    ``in_scale``: a vector over ``W_in``'s columns (a muP vector), or None.
    A slot's conv tail is ONE row, its ``d_conv - 1`` inputs of ``conv_dim``
    the oldest first: the tails ride as ``(layers x slots, (d_conv - 1) *
    conv_dim)`` (an axis of 3 taps among a pool's minor two is one the
    compiler may lay into the lanes, the pool then forty times its size), the
    SSD states as ``(layers x slots, H, P, N)``; ``at`` is the layer's slot(s)
    there."""

    d_ssm: int
    heads: int
    d_state: int
    n_groups: int
    d_conv: int
    eps: float
    dtype: object
    #: tokens a sub-chunk of ``ssd_chunk``, and the decode kernel's ``impl``
    sub: int
    impl: str
    in_scale: object = None

    @property
    def conv_dim(self) -> int:
        """The channels that go through the convolution: ``[x | B | C]``."""
        return self.d_ssm + 2 * self.n_groups * self.d_state

    def state_leaves(self, n: int, state_dtype) -> dict:
        """What a sequence holds of ``n`` such layers, for ``llm.cache.HybridPool``:
        name -> (layers, one slot's shape, dtype)."""
        return {
            "conv": (n, ((self.d_conv - 1) * self.conv_dim,), self.dtype),
            "ssd": (n, (self.heads, self.d_ssm // self.heads, self.d_state), state_dtype),
        }

    def init(self, keys, n: int, d: int, in_std, out_std, a_range, dt_range) -> dict:
        """Seeded parameters of ``n`` layers' mixers on a stream of ``d``.
        ``keys``: for the step size, ``W_in``, the taps, their bias (None: no
        bias), ``A`` and ``W_out``.  ``W_in`` normal at ``in_std`` (a number,
        or a vector over its columns), ``W_out`` at ``out_std``; ``A`` uniform
        in ``a_range``, the step size log-uniform in ``dt_range`` through
        ``dt_bias`` (its inverse softplus), ``D`` 1, the convolution (and its
        bias) uniform at ``d_conv ** -0.5``, the norm's scale 1."""
        k_step, k_in, k_taps, k_bias, k_a, k_out = keys
        lo, hi = math.log(dt_range[0]), math.log(dt_range[1])
        step = jnp.exp(jax.random.uniform(k_step, (n, self.heads)) * (hi - lo) + lo)
        taps = lambda k, shape: (jax.random.uniform(  # noqa: E731
            k, shape, jnp.float32, -1.0, 1.0) * self.d_conv**-0.5).astype(self.dtype)
        width = self.d_ssm + self.conv_dim + self.heads
        return {
            "ssm_in": {"kernel": normal_layers(k_in, n, (d, width), in_std, self.dtype)},
            "conv": {"kernel": taps(k_taps, (n, self.d_conv, self.conv_dim)),
                     "bias": (jnp.zeros((n, self.conv_dim), self.dtype) if k_bias is None
                              else taps(k_bias, (n, self.conv_dim)))},
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jax.random.uniform(k_a, (n, self.heads), jnp.float32, *a_range)),
            "D": jnp.ones((n, self.heads), jnp.float32),
            "ssm_norm": {"scale": jnp.ones((n, self.d_ssm), self.dtype)},
            "ssm_out": {"kernel": normal_layers(k_out, n, (self.d_ssm, d), out_std, self.dtype)},
        }

    def project(self, u, layer):
        """The input projection: (z (n, d_ssm) float32, ``[x | B | C]``
        before the convolution in the compute dtype, the step size (n, H)
        float32 after its softplus)."""
        z_end, conv_end = self.d_ssm, self.d_ssm + self.conv_dim
        p = dot32(u.astype(self.dtype), layer["ssm_in"]["kernel"])
        if self.in_scale is not None:
            p = p * self.in_scale
        step = jax.nn.softplus(p[:, conv_end:] + layer["dt_bias"].astype(jnp.float32))
        return p[:, :z_end], p[:, z_end:conv_end].astype(self.dtype), step

    def conv(self, taps, layer):
        """``taps``: the ``d_conv`` inputs of each of ``n`` outputs, the oldest
        first, each (n, conv_dim) -> SiLU of the causal depthwise convolution,
        float32, split into x (n, H, P), B and C (n, G, N)."""
        x_end, b_end = self.d_ssm, self.d_ssm + self.n_groups * self.d_state
        kern = layer["conv"]["kernel"].astype(jnp.float32)
        out = sum(t.astype(jnp.float32) * kern[i] for i, t in enumerate(taps))
        out = jax.nn.silu(out + layer["conv"]["bias"].astype(jnp.float32))
        rows = out.shape[0]
        return (out[:, :x_end].reshape(rows, self.heads, self.d_ssm // self.heads),
                out[:, x_end:b_end].reshape(rows, self.n_groups, self.d_state),
                out[:, b_end:].reshape(rows, self.n_groups, self.d_state))

    def out(self, y, z, layer):
        """Gate, THEN the norm within each group, then the output projection."""
        gated = (y.reshape(z.shape) * jax.nn.silu(z)).reshape(z.shape[0], self.n_groups, -1)
        normed = gated * jax.lax.rsqrt((gated * gated).mean(-1, keepdims=True) + self.eps)
        normed = normed.reshape(z.shape) * layer["ssm_norm"]["scale"].astype(jnp.float32)
        return dot32(normed.astype(self.dtype), layer["ssm_out"]["kernel"])

    @staticmethod
    def a(layer):
        return -jnp.exp(layer["A_log"].astype(jnp.float32))

    def decode(self, u, layer, conv, ssd, at, live):
        """One token of many sequences, row ``i`` on slot ``at[i]``; a row
        that is not ``live`` leaves its state alone.  Returns (the mixer's
        output (S, d) float32, conv, ssd)."""
        z, raw, step = self.project(u, layer)
        window = jnp.concatenate([conv[at], raw], axis=1)       # (S, d_conv * D), oldest first
        conv = conv.at[at].set(window[:, self.conv_dim:])
        xs, b, c = self.conv(jnp.split(window, self.d_conv, axis=1), layer)
        with jax.named_scope("ssd_update"):
            ssd, y = ssd_decode(ssd, xs, step, self.a(layer), b, c, layer["D"], at, live,
                                impl=self.impl)
        return self.out(y, z, layer), conv, ssd

    def chunk(self, u, layer, conv, ssd, at, fresh, n_valid, valid):
        """A chunk of ONE sequence on slot ``at``, its first ``n_valid`` rows
        real (``valid``).  A ``fresh`` chunk (the sequence's first) overwrites
        what the slot's last owner left; the last ``d_conv - 1`` valid inputs
        are what the next token needs.  Returns (the mixer's output (C, d)
        float32, conv, ssd)."""
        z, raw, step = self.project(u, layer)
        tail = jnp.where(fresh, 0, jax.lax.dynamic_index_in_dim(conv, at, 0, False))
        seq = jnp.concatenate([tail.reshape(-1, self.conv_dim), raw], axis=0)  # (taps + C, D)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jax.lax.dynamic_slice_in_dim(seq, n_valid, self.d_conv - 1).reshape(-1), at, 0)
        xs, b, c = self.conv([seq[i:i + len(raw)] for i in range(self.d_conv)], layer)
        with jax.named_scope("ssd_chunk"):
            s0 = jnp.where(fresh, 0.0, jax.lax.dynamic_index_in_dim(
                ssd, at, 0, False).astype(jnp.float32))
            y, s1 = ssd_chunk(s0, xs, step, self.a(layer), b, c, layer["D"], valid, sub=self.sub)
            ssd = jax.lax.dynamic_update_index_in_dim(ssd, s1.astype(ssd.dtype), at, 0)
        return self.out(y, z, layer), conv, ssd


def paged_kv_decode(k_pool, btab, positions, impl: str, pack=lambda x: x,
                    window: int | None = None):
    """The pool part of a decode's grouped-query layers: one position of each
    of many sequences (``btab`` (S, T) their block tables).  Returns
    ``step(q, k, v, k_pool, v_pool, base) -> (att, k_pool, v_pool)``: K and V
    written at the layer's ``base`` (its first block in the pools' flat
    view), each as ``pack`` lays a token's heads into the pool's rows, then
    the paged attention.  With a ``window`` a query sees the last ``window``
    keys alone, the walk starts at the first block it still sees
    (``ops.gqa_attention``) and the scope is ``window_attention``."""
    scope = "gqa_attention" if window is None else "window_attention"
    bs = k_pool.shape[3]
    phys = jnp.take_along_axis(btab, (positions // bs)[:, None], axis=1)[:, 0]
    write = _slots_write(phys, positions % bs, bs)

    def step(q, k, v, k_pool, v_pool, base):
        k_pool, v_pool = write(k_pool, pack(k), base), write(v_pool, pack(v), base)
        with jax.named_scope(scope):
            att = gqa_paged_attention(
                q, k_pool, v_pool, btab + base, positions, impl=impl, window=window)
        return att, k_pool, v_pool

    return step


def paged_kv_chunk(k_pool, btab, positions, start, n_valid, pack=lambda x: x,
                   window: int | None = None):
    """The same for a chunk of ONE sequence at ``positions`` (``start ..``),
    the first ``n_valid`` real: the chunk's K and V written, then its queries
    (in the pool's dtype) against the sequence's ``start + n_valid`` tokens
    (each against its last ``window`` alone, where one is given)."""
    write = _chunk_write(btab, start, n_valid, positions.shape[0], k_pool.shape[3])

    def step(q, k, v, k_pool, v_pool, base):
        k_pool, v_pool = write(k_pool, pack(k), base), write(v_pool, pack(v), base)
        with jax.named_scope("chunk_attention"):
            att = gqa_chunk_attention(
                q.astype(k_pool.dtype), k_pool, v_pool, btab + base, positions, start + n_valid,
                window)
        return att, k_pool, v_pool

    return step


def check_share(n_routed: int, offset: int, held: int, top_k: int) -> None:
    """A chip's share of an expert layer as a configuration states it: experts
    ``offset .. offset + held`` of ``n_routed``, ``top_k`` a token."""
    if offset + held > n_routed:
        raise ValueError("the held experts lie outside the router's width")
    if top_k > n_routed:
        raise ValueError("more experts a token than the router has")


def pattern_of(layer_types, n_layers: int, kinds: tuple) -> tuple:
    """``layer_types`` as a tuple, checked: one of ``kinds`` a layer, each
    kind somewhere (a pool with no layer has no shape)."""
    layer_types = tuple(layer_types)
    if len(layer_types) != n_layers or set(layer_types) != set(kinds):
        raise ValueError(f"layer_types names n_layers mixers, {' and '.join(kinds)} both")
    return layer_types


def runs_of(kinds) -> tuple:
    """A sequence of layer kinds as runs: ``((kind, how many), ...)``."""
    return tuple((kind, len(list(g))) for kind, g in itertools.groupby(kinds))


def pattern_layers(runs, stacks, x, arrays, mixers: dict, closings: dict, phase: str):
    """The layers of a body whose layers come in runs of one kind: ``runs``
    is ``((mixer, closing, how many), ...)`` and ``stacks[i]`` run ``i``'s
    layers stacked.  One ``_carry_loop`` a run, each over ALL of ``arrays``:
    the pools (a run leaves the other kinds' as they came), K first, and last
    the routed layer's ledger (``ops.moe``), which gets the step's count at
    the end.  ``mixers[mixer](h, layer, *pools, l) -> (h, *pools)`` is the
    ``l``-th layer of that mixer, ``closings[closing](h, layer, counts, m) ->
    (h, counts)`` the ``m``-th of that closing.  A run whose ``closing`` is
    None has layers that are a mixer alone: nothing is called for a closing,
    and nothing of it is counted.  Returns (x, arrays)."""
    n_blocks = arrays[0].shape[1]
    mixed, closed = collections.Counter(), collections.Counter()
    for (mixer, closing, n), stack in zip(runs, stacks):

        def layer_fn(h, layer, *carry, mix=mixers[mixer],
                     close=closing and closings[closing],
                     first=mixed[mixer], index=closed[closing]):
            *pools, counts, base = carry
            at = base // n_blocks  # the layer's place in its run
            h, *pools = mix(h, layer, *pools, first + at)
            if close:
                h, counts = close(h, layer, counts, index + at)
            return (h, *pools, counts)

        x, *arrays = _carry_loop(stack, x, tuple(arrays), layer_fn)
        mixed[mixer] += n
        closed[closing] += n
    return x, (*arrays[:-1], count_step(arrays[-1], phase))
