"""Mamba-1 selective scan: the decode update and the chunk form.

The recurrence, a channel ``c`` of ``D`` and a state index ``n`` of ``N``
(arXiv:2312.00752, section 3.2, zero-order hold on ``A`` and Euler on ``B``):

    s_t[n, c] = exp(delta_t[c] * A[n, c]) * s_{t-1}[n, c]
                + delta_t[c] * u_t[c] * B_t[n]
    m_t[c]    = sum_n s_t[n, c] * C_t[n] + D_skip[c] * u_t[c]

The state is held ``(N, D)``, NOT the paper's ``(D, N)``: ``D`` (thousands)
lies along the lanes and ``N`` (16) along the sublanes, so a float32 state
fills whole ``(8, 128)`` tiles; the other way round a TPU pads 16 lanes to
128, eight times the bytes in HBM and on every step.

Plain ``jax.numpy``: a decode is one fused elementwise update a row (read
the state, write it), a chunk a ``lax.scan`` over its tokens.  Both take
and return the state in float32; what the pool holds it in is the caller's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def scan_decode(s, u, delta, a, b, c, d_skip):
    """One token a row.  s: (rows, N, D) float32; u, delta: (rows, D); a:
    (N, D) (negative); b, c: (rows, N); d_skip: (D,).  Returns (s', m
    (rows, D)), float32."""
    u, delta, b, c = (x.astype(jnp.float32) for x in (u, delta, b, c))
    s = jnp.exp(delta[:, None, :] * a) * s + (delta * u)[:, None, :] * b[:, :, None]
    return s, (s * c[:, :, None]).sum(axis=1) + d_skip * u


def scan_chunk(s0, u, delta, a, b, c, d_skip, valid):
    """A chunk of ONE sequence, token after token.  s0: (N, D) float32; u,
    delta: (chunk, D); b, c: (chunk, N); valid: (chunk,) bool, false on the
    padded tail, whose tokens leave the state as it was.  Returns (m
    (chunk, D), s_last), float32."""
    u, delta, b, c = (x.astype(jnp.float32) for x in (u, delta, b, c))

    def step(s, xs):
        u_t, dt_t, b_t, c_t, ok = xs
        new = jnp.exp(dt_t * a) * s + (dt_t * u_t) * b_t[:, None]
        s = jnp.where(ok, new, s)
        return s, (s * c_t[:, None]).sum(axis=0)

    s, m = jax.lax.scan(step, s0, (u, delta, b, c, valid), unroll=8)
    return m + d_skip * u, s
