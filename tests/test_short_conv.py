"""``ops.short_conv``: the decode and the chunk form of LFM2's gated short
convolution against a plain loop over tokens, at small sizes on the CPU.

The loop carries the last ``taps - 1`` gated inputs of ONE sequence from zeros
and multiplies them, oldest first, by the taps.  Both forms work over a pool
of tails ``(layers * slots, taps - 1, d)`` that starts as NOISE: nothing may be
read of a slot before its sequence wrote it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.short_conv import short_conv_chunk, short_conv_decode

D, TAPS, SLOTS, LAYERS = 12, 3, 4, 2


def _taps(seed=0, taps=TAPS):
    return jax.random.uniform(jax.random.PRNGKey(seed), (taps, D), jnp.float32, -1.0, 1.0)


def _inputs(seed, n):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, D), jnp.float32)


def _token_loop(s, taps):
    tail, out = np.zeros((taps.shape[0] - 1, D), np.float32), []
    for row in np.asarray(s):
        window = np.concatenate([tail, row[None]], axis=0)
        out.append((window * np.asarray(taps)).sum(axis=0))
        tail = window[1:]
    return np.stack(out), tail


def _noise(taps=TAPS):
    return jnp.full((LAYERS * SLOTS, taps - 1, D), 9.0, jnp.float32)


def _chunks(tails, s, taps, at, size, start=0):
    """``s`` through the chunk form in pieces of ``size`` rows, the last one
    padded with noise."""
    out = []
    for pos in range(0, s.shape[0], size):
        piece = s[pos:pos + size]
        n = piece.shape[0]
        padded = jnp.concatenate([piece, jnp.full((size - n, D), 5.0)], axis=0)
        tails, c = short_conv_chunk(tails, padded, taps, at, start + pos == 0, n)
        out.append(np.asarray(c[:n]))
    return tails, np.concatenate(out)


@pytest.mark.parametrize("n,size", [(21, 8), (16, 8), (5, 8), (9, 1), (3, 2)])
def test_chunks_with_a_boundary_inside_the_sequence_and_a_padded_tail(n, size):
    s, taps, at = _inputs(1, n), _taps(), 1 * SLOTS + 2
    want, tail = _token_loop(s, taps)
    tails, got = _chunks(_noise(), s, taps, at, size)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # the last two VALID gated inputs go out, whatever the padding held
    np.testing.assert_array_equal(np.asarray(tails[at]), tail)
    # and no other slot is written
    others = np.delete(np.asarray(tails), at, axis=0)
    assert (others == 9.0).all()


@pytest.mark.parametrize("n_prompt", [0, 1, 2, 7])
def test_decodes_go_on_where_the_chunks_stopped(n_prompt):
    """Three rows of a decode batch: a dead row (the layer's trash slot), the
    sequence, and another sequence one token behind it in another slot."""
    layer, taps = 1, _taps(2)
    s, other = _inputs(3, n_prompt + 9), _inputs(4, n_prompt + 9)
    want, _ = _token_loop(s, taps)
    want_other, _ = _token_loop(other, taps)
    at, at_other, trash = layer * SLOTS + 3, layer * SLOTS + 1, layer * SLOTS
    tails = _noise()
    if n_prompt:
        tails, _ = _chunks(tails, s[:n_prompt], taps, at, 4)
        tails, _ = _chunks(tails, other[:n_prompt], taps, at_other, 4)
    else:  # a sequence that starts in a decode starts from zeros
        tails = tails.at[jnp.array([at, at_other])].set(0.0)
    rows = jnp.array([trash, at, at_other])
    for i in range(n_prompt, n_prompt + 9):
        batch = jnp.stack([jnp.full((D,), 3.0), s[i], other[i]])
        tails, c = short_conv_decode(tails, batch, taps, rows)
        np.testing.assert_allclose(c[1], want[i], atol=1e-6)
        np.testing.assert_allclose(c[2], want_other[i], atol=1e-6)
    # the dead row wrote the trash slot of ITS layer and nothing else
    assert (np.asarray(tails[:SLOTS]) == 9.0).all()
    assert (np.asarray(tails[layer * SLOTS + 2]) == 9.0).all()


def test_a_reused_slot_reads_nothing_of_its_last_owner():
    taps, at = _taps(5), 2
    tails, _ = _chunks(_noise(), _inputs(6, 11), taps, at, 4)
    s = _inputs(7, 6)
    want, tail = _token_loop(s, taps)
    tails, got = _chunks(tails, s, taps, at, 4)   # start == 0: fresh
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tails[at]), tail)
    # a chunk that is NOT the sequence's first reads what the slot holds
    _, resumed = _chunks(tails, s, taps, at, 4, start=6)
    assert np.abs(resumed[:2] - want[:2]).max() > 1e-3


def test_two_dead_rows_meet_in_the_trash_slot_and_a_live_row_is_untouched():
    taps, s = _taps(8), _inputs(9, 4)
    tails = _noise().at[3].set(0.0)
    rows = jnp.array([0, 0, 3])
    for i in range(4):
        batch = jnp.stack([jnp.ones((D,)), -jnp.ones((D,)), s[i]])
        tails, c = short_conv_decode(tails, batch, taps, rows)
    np.testing.assert_allclose(c[2], _token_loop(s, taps)[0][3], atol=1e-6)


@pytest.mark.parametrize("taps", [2, 4])
def test_other_widths_of_the_convolution(taps):
    kernel = jax.random.uniform(jax.random.PRNGKey(taps), (taps, D), jnp.float32, -1.0, 1.0)
    s = _inputs(10, 13)
    want, tail = _token_loop(s, kernel)
    tails, got = _chunks(_noise(taps), s[:9], kernel, 1, 4)
    for i in range(9, 13):
        tails, c = short_conv_decode(tails, s[i][None], kernel, jnp.array([1]))
        got = np.concatenate([got, np.asarray(c)])
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tails[1]), tail)


def test_the_tails_are_kept_in_the_pools_dtype_and_the_sum_is_float32():
    """Both forms round a gated input to the pool's dtype BEFORE the
    convolution, the current token's too: a token's result is the same
    whether its neighbours came from the pool or from the chunk."""
    taps, s = _taps(11), _inputs(12, 10)
    pool = jnp.zeros((SLOTS, TAPS - 1, D), jnp.bfloat16)
    want, _ = _token_loop(s.astype(jnp.bfloat16).astype(jnp.float32), taps)
    tails, got = _chunks(pool, s[:6], taps, 2, 4)
    assert tails.dtype == jnp.bfloat16 and got.dtype == np.float32
    for i in range(6, 10):
        tails, c = short_conv_decode(tails, s[i][None], taps, jnp.array([2]))
        got = np.concatenate([got, np.asarray(c)])
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert np.abs(got - _token_loop(s, taps)[0]).max() > 1e-4  # the rounding is there
