"""``ops.ssd`` (Mamba-2's decode update and chunk form) and
``ops.gqa_attention`` (grouped-query attention over a block table) against
the recurrence token by token and against hand-written cases, float32 on
the CPU (the Pallas kernels interpreted)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gqa_attention as ga
from ray_tpu.ops import ssd


def _inputs(seed, t, h=4, p=8, n=16, g=2):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, b, c = f(t, h, p), f(t, g, n), f(t, g, n)
    dt = jnp.abs(f(t, h)) * 0.3 + 1e-3
    a, skip = -jnp.abs(f(h)) - 0.5, f(h)
    return x, dt, a, b, c, skip, f(h, p, n)


def _scan(s0, x, dt, a, b, c, d_skip, valid):
    """The recurrence itself, token after token: what ``ssd_chunk`` and
    ``ssd_decode`` are held to.  Shapes as ``ssd_chunk``."""
    f32 = jnp.float32
    H, G = x.shape[1], b.shape[1]
    x, dt, a = x.astype(f32), dt.astype(f32), a.astype(f32)
    bh = jnp.repeat(b.astype(f32), H // G, axis=1)
    ch = jnp.repeat(c.astype(f32), H // G, axis=1)

    def step(s, xs):
        x_t, dt_t, b_t, c_t, ok = xs
        new = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        s = jnp.where(ok, new, s)
        return s, (s * c_t[:, None, :]).sum(axis=-1)

    s, y = jax.lax.scan(step, s0.astype(f32), (x, dt, bh, ch, valid))
    return y + d_skip.astype(f32)[:, None] * x, s


@pytest.mark.parametrize("chunk,sub", [(48, 16), (16, 16), (8, 128)])
def test_the_chunk_form_equals_the_recurrence_from_a_nonzero_state(chunk, sub):
    """Entered with a state that is not zero, ended on a padded tail: the
    matrix-product form gives the token loop's outputs and leaves the state
    after the last VALID token."""
    t = chunk - 5
    x, dt, a, b, c, skip, s0 = _inputs(0, chunk)
    valid = jnp.arange(chunk) < t
    want, s_want = _scan(s0, x[:t], dt[:t], a, b[:t], c[:t], skip, valid[:t])
    got, s_got = ssd.ssd_chunk(s0, x, dt, a, b, c, skip, valid, sub=sub)
    np.testing.assert_allclose(got[:t], want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s_got, s_want, rtol=2e-5, atol=2e-5)
    assert np.abs(np.asarray(s_want - s0)).max() > 0.1  # the chunk moved the state


def test_two_chunks_carry_the_state_as_one_does():
    x, dt, a, b, c, skip, s0 = _inputs(1, 32)
    ok = jnp.ones(16, bool)
    y1, s1 = ssd.ssd_chunk(s0, x[:16], dt[:16], a, b[:16], c[:16], skip, ok, sub=4)
    y2, s2 = ssd.ssd_chunk(s1, x[16:], dt[16:], a, b[16:], c[16:], skip, ok, sub=4)
    want, s_want = _scan(s0, x, dt, a, b, c, skip, jnp.ones(32, bool))
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s2, s_want, rtol=2e-5, atol=2e-5)


def test_a_chunk_that_is_no_whole_number_of_subchunks_is_refused():
    x, dt, a, b, c, skip, s0 = _inputs(2, 12)
    with pytest.raises(ValueError, match="sub-chunks"):
        ssd.ssd_chunk(s0, x, dt, a, b, c, skip, jnp.ones(12, bool), sub=8)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_decode_update_equals_the_recurrence(impl):
    """Rows on scattered slots, one of them dead: each live row's state and
    output are the loop's one step on; the dead row's slot and every slot no
    row owns keep their (NaN) content."""
    rows, h, p, n = 4, 4, 8, 16
    x, dt, a, b, c, skip, _ = _inputs(3, rows, h, p, n)
    rng = np.random.default_rng(4)
    pool = np.full((7, h, p, n), np.nan, np.float32)
    slots = np.array([5, 2, 0, 6], np.int32)
    live = np.array([True, True, False, True])
    pool[slots[live]] = rng.normal(size=(3, h, p, n))
    state, y = jax.jit(lambda *args: ssd.ssd_decode(*args, impl=impl))(
        jnp.asarray(pool), x, dt, a, b, c, skip, jnp.asarray(slots), jnp.asarray(live))
    for r in np.flatnonzero(live):
        want, s_want = _scan(jnp.asarray(pool[slots[r]]), x[r:r + 1], dt[r:r + 1], a,
                                    b[r:r + 1], c[r:r + 1], skip, jnp.ones(1, bool))
        np.testing.assert_allclose(y[r], want[0], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(state[slots[r]], s_want, rtol=2e-5, atol=2e-5)
    untouched = np.setdiff1d(np.arange(7), slots[live])
    assert np.isnan(np.asarray(state)[untouched]).all()
    np.testing.assert_allclose(y[2], skip[:, None] * x[2])  # a dead row reads D x alone


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_decode_with_no_live_row_leaves_the_pool_as_it_was(impl):
    x, dt, a, b, c, skip, _ = _inputs(5, 2)
    pool = jnp.asarray(np.random.default_rng(6).normal(size=(3, 4, 8, 16)), jnp.float32)
    state, _ = ssd.ssd_decode(pool, x, dt, a, b, c, skip, jnp.zeros(2, jnp.int32),
                              jnp.zeros(2, bool), impl=impl)
    np.testing.assert_array_equal(state, pool)


def test_heads_a_block_of_the_decode_kernel():
    assert ssd.heads_per_block(32, 128, 256) == 8       # 1 MB of float32 a grid step
    assert ssd.heads_per_block(4, 8, 16) == 4           # a tiny state: the row whole
    assert ssd.heads_per_block(24, 128, 256) == 8


# -- grouped-query attention -------------------------------------------------------


def _dense_gqa(q, k, v, mask):
    """Hand-written: query head i reads key-value head i // (H / K)."""
    c, h, e = q.shape
    group = h // k.shape[1]
    out = np.zeros((c, h, e), np.float32)
    for i in range(h):
        scores = np.asarray(q[:, i]) @ np.asarray(k[:, i // group]).T / np.sqrt(e)
        scores = np.where(mask, scores, -np.inf)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        out[:, i] = (probs / probs.sum(-1, keepdims=True)) @ np.asarray(v[:, i // group])
    return out


def _paged(seed, rows, heads=10, kv=2, e=8, block=4, tmax=6, blocks=40):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    tables = jnp.asarray(rng.permutation(blocks - 1)[:rows * tmax].reshape(rows, tmax) + 1,
                         jnp.int32)
    return f(rows, heads, e), f(blocks, kv, block, e), f(blocks, kv, block, e), tables


def _tokens(pool, table):
    return pool[table].transpose(0, 2, 1, 3).reshape(-1, pool.shape[1], pool.shape[3])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_five_query_heads_a_key_value_head_in_a_decode(impl):
    q, k_pool, v_pool, tables = _paged(0, rows=3)
    positions = jnp.asarray([5, 0, 22], jnp.int32)
    got = ga.gqa_paged_attention(q, k_pool, v_pool, tables, positions, impl=impl)
    for r in range(3):
        mask = (np.arange(24) <= int(positions[r]))[None, :]
        want = _dense_gqa(q[r:r + 1], _tokens(k_pool, tables[r]), _tokens(v_pool, tables[r]),
                          mask)
        np.testing.assert_allclose(got[r], want[0], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("start,n_valid", [(0, 8), (4, 8), (13, 3)])
def test_a_chunks_attention_walks_the_table_in_runs(start, n_valid, monkeypatch):
    """Runs of 2 blocks over a table of 6: the running maximum and sum give
    the dense causal softmax's numbers, blocks past the chunk's last valid
    token are never read (NaN there), and a ragged last run is masked."""
    monkeypatch.setattr(ga, "_RUN_TOKENS", 8)
    q, k_pool, v_pool, tables = _paged(1, rows=8, blocks=60)
    table = tables[0]
    positions = start + jnp.arange(8, dtype=jnp.int32)
    n_ctx = start + n_valid
    past = np.asarray(table)[-(-n_ctx // 8) * 2:]      # blocks of runs the walk never reaches
    k_pool = k_pool.at[past].set(jnp.nan)
    v_pool = v_pool.at[past].set(jnp.nan)
    got = ga.gqa_chunk_attention(q, k_pool, v_pool, table, positions, n_ctx)
    mask = np.arange(24)[None, :] <= np.asarray(positions)[:, None]
    clean = lambda a: jnp.nan_to_num(a)  # noqa: E731
    want = _dense_gqa(q, _tokens(clean(k_pool), table), _tokens(clean(v_pool), table), mask)
    np.testing.assert_allclose(got[:n_valid], want[:n_valid], rtol=2e-5, atol=2e-5)


def test_rotary_over_the_whole_head_in_halves():
    """Lane i turns with lane i + e / 2 by ``position * theta ** (-2i / e)``:
    position 0 is the identity, and a quarter turn at the first frequency
    swaps the pair."""
    e, theta = 4, 1e4
    x = jnp.asarray([[[1.0, 2.0, 3.0, 4.0]]])
    np.testing.assert_allclose(ga.rotary_half(x, jnp.asarray([0]), theta), x)
    quarter = jnp.asarray([np.pi / 2])          # frequency 0 is 1: a quarter turn
    got = np.asarray(ga.rotary_half(x, quarter, theta))[0, 0]
    ang = np.pi / 2 * theta ** -0.5             # the second pair's angle
    np.testing.assert_allclose(got[[0, 2]], [-3.0, 1.0], atol=1e-6)
    np.testing.assert_allclose(
        got[[1, 3]], [2 * np.cos(ang) - 4 * np.sin(ang), 4 * np.cos(ang) + 2 * np.sin(ang)],
        rtol=1e-6)
    del e
