"""LFM2's gated short convolution: a depthwise causal convolution of a few
taps over GATED inputs, the one thing its mixer remembers.

The mixer of a ``conv`` layer, on the normed stream ``u`` (T, d)::

    [B | C | x] = u W_in                 (d -> 3 d, split in that order)
    s_t = B_t * x_t                      (the gated input)
    c_t = sum_{j < taps} w_j * s_{t - (taps - 1) + j}     (zeros before the start)
    Mixer = (C * c) W_out

The projections and the two gates are the family's (``models.lfm2``); here is
the part with memory.  What a sequence carries from one token to the next is
its TAILS, the last ``taps - 1`` gated inputs of every channel (2 x 2,048 at
the published sizes: 8 KB a slot a layer in bfloat16, the smallest state the
engine holds).  Two forms over one pool of tails ``(layers * slots, taps - 1,
d)``, both agreeing with a loop over tokens (``tests/test_short_conv.py``):

* ``short_conv_decode`` -- one token of MANY sequences: row ``i`` reads and
  writes the tails at ``at[i]`` (``layer * slots + slot``).  A dead row's
  ``at`` is its layer's trash slot, which no live row reads.
* ``short_conv_chunk`` -- ``C`` consecutive tokens of ONE sequence, the first
  ``n_valid`` real: the slot's tails come in unless the sequence starts here
  (``fresh``: what the slot's last owner left is never read), and the last
  ``taps - 1`` VALID gated inputs go out, whatever the padding holds.

Plain ``jax.numpy``: a decode moves ``2 x (taps - 1) x d`` numbers a row a
layer beside ``W_in`` and ``W_out``'s 16.8M, and a chunk's convolution is
``taps`` shifted multiply-adds of an array it has just made.  The gated inputs
are rounded to the pool's dtype BEFORE the convolution in both forms, the
current token's too, so a token's result does not depend on whether its
neighbours came from the pool or from the chunk; the sum is float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _convolve(window, taps):
    """window: (..., taps - 1 + n, d), the oldest first; taps: (taps, d).
    The convolution at the last ``n`` positions, float32."""
    n = window.shape[-2] - taps.shape[0] + 1
    w32, k32 = window.astype(jnp.float32), taps.astype(jnp.float32)
    return sum(w32[..., j:j + n, :] * k32[j] for j in range(taps.shape[0]))


def short_conv_decode(tails, s, taps, at):
    """tails: (layers * slots, taps - 1, d); s: (S, d) this token's gated
    input a row; taps: (taps, d); at: (S,) int32.  Returns (tails, c (S, d)
    float32)."""
    window = jnp.concatenate([tails[at], s.astype(tails.dtype)[:, None, :]], axis=1)
    return tails.at[at].set(window[:, 1:]), _convolve(window, taps)[:, 0]


def short_conv_chunk(tails, s, taps, at, fresh, n_valid):
    """tails: as above; s: (C, d) the gated inputs of ``C`` consecutive
    tokens of the sequence whose tails lie at ``at`` (a scalar), the first
    ``n_valid`` real; ``fresh``: the sequence starts with this chunk.
    Returns (tails, c (C, d) float32)."""
    held = taps.shape[0] - 1
    tail = jnp.where(fresh, 0, jax.lax.dynamic_index_in_dim(tails, at, 0, keepdims=False))
    seq = jnp.concatenate([tail, s.astype(tails.dtype)], axis=0)        # (held + C, d)
    # the valid inputs end at ``held + n_valid`` of ``seq``; with fewer than
    # ``held`` of them the older tails move up
    last = jax.lax.dynamic_slice_in_dim(seq, n_valid, held)
    return jax.lax.dynamic_update_index_in_dim(tails, last, at, 0), _convolve(seq, taps)
