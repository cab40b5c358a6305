"""Attention kernels: Pallas flash (interpret mode on CPU) and ring
attention over the sp mesh axis must agree with the XLA reference path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import causal_attention, _xla_attention
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.flash_attention import flash_attention


def _qkv(b=2, h=4, s=256, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (b, h, s, d), dtype) for k in ks]


def _f32_reference(q, k, v, w):
    """Dense causal attention and its three gradients in float32, whatever
    the inputs' dtype."""
    f32 = [t.astype(jnp.float32) for t in (q, k, v)]
    loss = lambda q, k, v: (_xla_attention(q, k, v) * w).sum()  # noqa: E731
    return _xla_attention(*f32), jax.grad(loss, argnums=(0, 1, 2))(*f32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("seq,block_q,block_k", [
    (1024, 1024, 1024),  # gpt2m_train's case: ONE grid cell a head, the diagonal one, 4 x 4 sub-tiles
    (512, 512, 512),     # the same with 2 x 2 sub-tiles
    (1024, 512, 512),    # 2 blocks: one grid cell below the diagonal, run as ONE unmasked tile
    (2048, 512, 512),    # 4 blocks: six cells below the diagonal, six dead
    (1024, 256, 512),    # unequal blocks of whole sub-tiles: the diagonal crosses cells at two offsets
    (1024, 512, 256),    # ... and with the kv block the smaller one (negative offsets)
    (256, 64, 128),      # blocks smaller than one sub-tile, unequal
    (128, 64, 64),       # blocks smaller than one sub-tile, a 2 x 2 grid
    (192, 128, 128),     # seq 192 isn't divisible by 128: _pick_blocks must shrink to 64
])
def test_flash_matches_dense(seq, block_q, block_k, dtype):
    """Forward AND the three gradients of the sub-tiled kernels against
    dense attention: float32 at the kernels' own tolerances, bf16 against
    the float32 dense reference of the same inputs."""
    q, k, v = _qkv(b=1, h=2, s=seq, dtype=dtype)
    w = jnp.cos(jnp.arange(64, dtype=jnp.float32))
    ref, g_ref = _f32_reference(q, k, v, w)

    def loss(q, k, v):
        return (flash_attention(q, k, v, block_q, block_k).astype(jnp.float32) * w).sum()

    out = flash_attention(q, k, v, block_q=block_q, block_k=block_k)
    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert out.dtype == dtype and all(g.dtype == dtype for g in grads)
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        for g, r in zip(grads, g_ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-5)
    else:  # one bf16 rounding of the result (and of ``out`` before delta)
        np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref), atol=2e-2, rtol=1e-2)
        for g, r in zip(grads, g_ref):
            np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(r), atol=4e-2, rtol=2e-2)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _dense_packed(qkv, heads):
    """Dense causal attention over a fused projection's output, through the
    XLA path: split, head-major, attend, merge."""
    b, s, width = qkv.shape
    hd = width // (3 * heads)
    q, k, v = (t.reshape(b, s, heads, hd).transpose(0, 2, 1, 3) for t in jnp.split(qkv, 3, axis=-1))
    return _xla_attention(q, k, v).transpose(0, 2, 1, 3).reshape(b, s, heads * hd)


@pytest.mark.parametrize("block_q,block_k", [
    (128, 256),  # 4 x 2 grid cells: dq^T waits in its scratch across kv blocks, delta is kept from the first
    (256, 128),  # 2 x 4, the kv block the smaller: a q block's dq is done before the kv blocks are
    (512, 512),  # ONE grid block (gpt2m_train's case): every accumulator a value, nothing revisited
], ids=["grid_128x256", "grid_256x128", "one_block"])
@pytest.mark.parametrize("head_dim,heads,per_block", [
    (64, 4, 2), (64, 6, 2),    # two heads a 128-lane block: two and three column blocks
    (128, 2, 1), (128, 3, 1),  # one head a block
    (256, 2, 1), (256, 3, 1),  # a head is one block of 256 lanes
    (16, 2, 2),                # tiny widths (CPU tests' models): the heads share one narrow block
])
def test_flash_packed_matches_dense(head_dim, heads, per_block, block_q, block_k):
    """The column-blocked entry reads q, k and v out of ONE (batch, seq, 3 x
    heads x head_dim) array and answers in (batch, seq, heads x head_dim):
    the forward against the dense softmax and the ONE packed gradient (dq,
    dk and dv, the one backward kernel's three column blocks) against
    ``jax.grad`` of the XLA path, over a sequence of one grid block and of
    several (cells on, below and above the diagonal)."""
    assert fa._heads_per_block(heads, head_dim) == per_block
    qkv = jax.random.normal(jax.random.PRNGKey(3), (2, 512, 3 * heads * head_dim), jnp.float32)
    w = jnp.cos(jnp.arange(heads * head_dim, dtype=jnp.float32))
    out = fa.flash_attention_packed(qkv, heads, block_q=block_q, block_k=block_k)
    assert out.shape == (2, 512, heads * head_dim)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_dense_packed(qkv, heads)), atol=2e-5)
    grad = jax.grad(
        lambda x: (fa.flash_attention_packed(x, heads, block_q=block_q, block_k=block_k) * w).sum())(qkv)
    ref = jax.grad(lambda x: (_dense_packed(x, heads) * w).sum())(qkv)
    assert grad.shape == qkv.shape
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref), atol=5e-5)


@pytest.mark.parametrize("seq,block", [(256, 256), (512, 256)], ids=["one_step", "grid"])
def test_two_heads_of_a_lane_block_do_not_leak(seq, block):
    """Two heads of 64 share a 128-lane column block and every product runs
    over the block's whole width: head A's output and gradients must not
    move when head B's q, k, v and cotangent change."""
    heads, hd = 2, 64
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    qkv = jax.random.normal(keys[0], (1, seq, 3 * heads * hd), jnp.float32)
    do = jax.random.normal(keys[1], (1, seq, heads * hd), jnp.float32)
    lane = jnp.arange(heads * hd)
    head_b = jnp.tile(lane >= hd, 3)  # head B's columns of q, k and v
    other = jnp.where(head_b, 3.0 * jax.random.normal(keys[2], qkv.shape), qkv)
    other_do = jnp.where(lane >= hd, jax.random.normal(keys[3], do.shape), do)

    def run(x, d):
        f = lambda x: fa.flash_attention_packed(x, heads, block_q=block, block_k=block)  # noqa: E731
        out, vjp = jax.vjp(f, x)
        return out, vjp(d)[0]

    (out, grad), (out2, grad2) = run(qkv, do), run(other, other_do)
    assert float(jnp.abs(out - out2)[..., hd:].max()) > 0.1  # head B did change
    np.testing.assert_array_equal(np.asarray(out[..., :hd]), np.asarray(out2[..., :hd]))
    np.testing.assert_array_equal(np.asarray(grad[..., ~head_b]), np.asarray(grad2[..., ~head_b]))


def test_flash_packed_refuses_what_it_cannot_tile(monkeypatch):
    """On a TPU a column block must be whole 128-lane tiles: an odd head
    count at width 64 leaves one head a block and raises, as unaligned
    sequence blocks do; never a silent second path."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    assert fa._heads_per_block(3, 64) == 1
    with pytest.raises(ValueError, match="tile by 128"):
        fa.flash_attention_packed(jnp.zeros((1, 128, 3 * 3 * 64), jnp.bfloat16), 3)
    with pytest.raises(ValueError, match="tile by 128"):
        fa.flash_attention(*[jnp.zeros((1, 3, 128, 64), jnp.bfloat16)] * 3)
    with pytest.raises(ValueError, match="not 3 x 4 heads"):
        fa.flash_attention_packed(jnp.zeros((1, 128, 200), jnp.bfloat16), 4)


def test_gpt_block_hands_the_kernels_the_projection_as_it_is():
    """``models.gpt``'s block under ``attn_impl="flash"``: the traced loss
    and its gradient hold the two kernels and NO transpose, split or
    concatenate of a head (the kernels read the fused projection's own
    output and write what ``attn_out`` reads); and it agrees with the XLA
    path, loss and gradients."""
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss

    kw = dict(vocab_size=128, seq_len=128, d_model=128, n_layers=2, n_heads=2, dtype="float32",
              remat_policy="attn")
    flash, xla = GPTConfig(attn_impl="flash", **kw), GPTConfig(attn_impl="xla", **kw)
    params = gpt_init(jax.random.PRNGKey(0), flash)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, 128, jnp.int32)
    step = lambda cfg: jax.value_and_grad(lambda p: gpt_loss(cfg, p, tokens))  # noqa: E731

    def traced(cfg):
        eqns = list(_eqns(jax.make_jaxpr(step(cfg))(params).jaxpr))
        head_major = [e for e in eqns if e.primitive.name == "transpose"
                      and len(e.invars[0].aval.shape) == 4]
        return {e.primitive.name for e in eqns}, head_major, eqns

    names, head_major, eqns = traced(flash)
    kernels = {e.params["name"] for e in eqns if e.primitive.name == "pallas_call"}
    assert kernels == {"flash_fwd", "flash_bwd"}
    assert not {"split", "concatenate"} & names and head_major == []
    names_xla, head_major_xla, _ = traced(xla)  # what the check would see if it were there
    assert "split" in names_xla and head_major_xla
    (l_f, g_f), (l_x, g_x) = step(flash)(params), step(xla)(params)
    np.testing.assert_allclose(float(l_f), float(l_x), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_f), jax.tree_util.tree_leaves(g_x)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def _brute_tiles(block_q, block_k, sub_q, sub_k, off):
    """(i, j, crossed) of the sub-tiles with a live score, from the mask
    itself; ``off`` None is a cell wholly below the diagonal."""
    rows = (block_k if off is None else off) + np.arange(block_q)[:, None]
    live = np.arange(block_k)[None, :] <= rows
    tiles = live.reshape(block_q // sub_q, sub_q, block_k // sub_k, sub_k).transpose(0, 2, 1, 3)
    return [(i, j, not tiles[i, j].all())
            for i in range(tiles.shape[0]) for j in range(tiles.shape[1]) if tiles[i, j].any()]


@pytest.mark.parametrize("block_q,block_k,sub_q,sub_k,n_diag", [
    (1024, 1024, 256, 256, 10),  # gpt2m_train: 10 of 16
    (1024, 1024, 256, 128, 20),  # 20 of 32
    (1024, 1024, 128, 128, 36),  # 36 of 64
    (512, 256, 128, 128, None),  # unequal blocks: two crossed offsets, 0 and -256
    (64, 128, 64, 128, None),    # a block smaller than a sub-tile is ONE sub-tile
])
def test_flash_issues_only_live_sub_tiles(block_q, block_k, sub_q, sub_k, n_diag):
    """The skipping is seen, not only timed: the sub-tile products a kernel
    body issues (``_live_tiles``, which both kernels build their loops
    from) are exactly the sub-tiles that hold a live score, for a cell on
    the diagonal and for one below it; and ``_diag_offsets`` names exactly
    the grid cells the diagonal crosses."""
    offsets = fa._diag_offsets(block_q, block_k)
    seq = 4 * max(block_q, block_k)
    crossed, whole = set(), set()
    for q0 in range(0, seq, block_q):
        for k0 in range(0, seq, block_k):
            live = np.arange(k0, k0 + block_k)[None, :] <= np.arange(q0, q0 + block_q)[:, None]
            if live.any():
                (whole if live.all() else crossed).add(q0 - k0)
    assert crossed == set(offsets)
    assert all(off >= block_k - 1 for off in whole)  # how _walk_cell tells them
    for off in offsets + [None]:
        tiles = fa._live_tiles(block_q, block_k, sub_q, sub_k, off)
        assert tiles == _brute_tiles(block_q, block_k, sub_q, sub_k, off)
    total = (block_q // sub_q) * (block_k // sub_k)
    assert len(fa._live_tiles(block_q, block_k, sub_q, sub_k, None)) == total
    if n_diag is not None:
        assert len(fa._live_tiles(block_q, block_k, sub_q, sub_k, 0)) == n_diag < total


def _kernel_bodies(seq, count):
    """``{kernel name: count(equations of its body)}`` of the traced
    gradient of a head-major call at ``seq``."""
    x = jax.ShapeDtypeStruct((1, 1, seq, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: fa.flash_attention(q, k, v).sum().astype(
        jnp.float32), argnums=(0, 1, 2)))(x, x, x)
    return {
        eqn.params["name"]: count(list(_eqns(eqn.params["jaxpr"])))
        for eqn in _eqns(jaxpr.jaxpr) if eqn.primitive.name == "pallas_call"
    }


@pytest.mark.parametrize("seq", [1024, 2048])
def test_flash_kernel_bodies_hold_only_live_products(seq):
    """Count the matrix products in the two kernels' bodies as traced: 2 a
    live sub-tile of the diagonal case in the forward and 5 in the ONE
    backward (scores and ``do v^T`` once, then dv, dk and dq from them: the
    dQ and dK/dV kernels it replaces held 3 + 4), and where the grid has
    cells below the diagonal 2 and 5 more for the whole case, which runs as
    ONE tile; nothing for a dead sub-tile or a dead cell."""
    block = 1024
    sub_q, sub_k = fa._sub_tiles(block, block)
    live = len(fa._live_tiles(block, block, sub_q, sub_k, 0))
    if seq > block:  # a grid of ONE cell a head does not even trace the whole case
        live += len(fa._live_tiles(block, block, block, block, None))
    assert live == (10 if seq == block else 11)
    dots = _kernel_bodies(seq, lambda es: sum(e.primitive.name == "dot_general" for e in es))
    assert dots == {"flash_fwd": 2 * live, "flash_bwd": 5 * live}


@pytest.mark.parametrize("seq", [1024, 2048])
def test_flash_backward_makes_a_tile_s_softmax_weights_once(seq):
    """ONE ``exp`` pass a live sub-tile in the backward (the two kernels it
    replaces made every tile's weights twice), two in the forward (the
    weights and the running maximum's correction); and the backward is one
    ``pallas_call`` with ONE result, the packed gradient: ``delta`` stays in
    the kernel's scratch and never reaches HBM."""
    live = 10 if seq == 1024 else 11
    exps = _kernel_bodies(seq, lambda es: sum(e.primitive.name == "exp" for e in es))
    assert exps == {"flash_fwd": 2 * live, "flash_bwd": live}
    qkv = jax.ShapeDtypeStruct((2, seq, 3 * 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x: fa.flash_attention_packed(x, 2).sum().astype(jnp.float32)))(qkv)
    (bwd,) = [e for e in _eqns(jaxpr.jaxpr)
              if e.primitive.name == "pallas_call" and e.params["name"] == "flash_bwd"]
    assert [v.aval.shape for v in bwd.outvars] == [(2, seq, 3 * 128)]


@pytest.mark.parametrize("seq,width,n,block,most_mb", [
    (1024, 128, 2, 1024, 16),   # gpt2m_train: what Mosaic gives unasked
    (4096, 128, 2, 1024, 28),   # a whole 1024 x 1024 cell below the diagonal, dq^T of 2 MB
    (8192, 128, 2, 1024, 30),   # dq^T of 4 MB
    (4096, 256, 1, 1024, 40),   # a head of 256 lanes
    (131072, 128, 2, 1024, 100),  # dq^T of 64 MB: the longest the budget carries
])
def test_flash_backward_asks_for_the_fast_memory_its_shapes_need(seq, width, n, block, most_mb):
    """dq^T of the head block's WHOLE sequence lives in the one backward
    kernel's scratch, so its fast memory grows with the sequence: the
    kernel asks for it from ``seq``, the block's width and the grid blocks,
    never under Mosaic's own default, and the public entries refuse a
    sequence whose dq^T no core could hold."""
    need = fa._bwd_vmem_bytes(seq, width, n, block, block, 2)
    assert fa._VMEM_DEFAULT <= need <= most_mb * 2**20
    assert need >= seq * width * 4 or need == fa._VMEM_DEFAULT
    assert need <= fa._VMEM_MOST


def test_flash_refuses_a_sequence_its_backward_cannot_hold(monkeypatch):
    """On a TPU a sequence whose dq^T does not fit a core's fast memory is
    refused by name, before any kernel is built; the interpreter takes it."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    with pytest.raises(ValueError, match="fast memory"):
        fa._blocks(2**18, 16, 64, None, None, None, None)
    assert fa._blocks(2**17, 16, 64, None, None, None, None) == (1024,) * 4


def test_causal_attention_auto_dispatch_small_seq():
    # tiny seq takes the XLA path; result identical either way
    q, k, v = _qkv(s=64)
    np.testing.assert_allclose(
        np.asarray(causal_attention(q, k, v, impl="auto")),
        np.asarray(_xla_attention(q, k, v)),
        atol=1e-6,
    )


def test_flash_sharded_matches_dense():
    """sp=1 multi-device mesh (dp=2, tp=2): the shard_map'd Pallas kernel
    must agree with dense attention, forward and gradients."""
    from jax.sharding import Mesh

    from ray_tpu.ops.flash_attention import flash_attention_sharded, flash_shardable

    devs = np.array(jax.devices()[:4]).reshape(2, 1, 2, 1)
    mesh = Mesh(devs, ("dp", "fsdp", "tp", "sp"))
    q, k, v = _qkv(b=2, h=4, s=128, d=32)
    assert flash_shardable(2, 4, mesh)
    assert not flash_shardable(3, 4, mesh)
    ref = _xla_attention(q, k, v)
    w = jnp.cos(jnp.arange(32))
    with mesh:
        out = jax.jit(lambda q, k, v: flash_attention_sharded(q, k, v, mesh))(q, k, v)
        g_sh = jax.jit(
            jax.grad(
                lambda q, k, v: (flash_attention_sharded(q, k, v, mesh) * w).sum(),
                argnums=(0, 1, 2),
            )
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    g_ref = jax.grad(lambda q, k, v: (_xla_attention(q, k, v) * w).sum(), argnums=(0, 1, 2))(
        q, k, v
    )
    for a, b in zip(g_ref, g_sh):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-5)


def test_ring_attention_matches_dense():
    """sp=2 ring attention over the virtual CPU mesh == dense causal."""
    from jax.sharding import Mesh, PartitionSpec as P

    from ray_tpu.ops.ring_attention import ring_attention_sharded

    devs = np.array(jax.devices()[:8]).reshape(2, 1, 2, 2)
    mesh = Mesh(devs, ("dp", "fsdp", "tp", "sp"))
    q, k, v = _qkv(b=2, h=4, s=256, d=32)
    ref = _xla_attention(q, k, v)
    with mesh:
        out = jax.jit(lambda q, k, v: ring_attention_sharded(q, k, v, mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_grads_match_dense():
    from jax.sharding import Mesh

    from ray_tpu.ops.ring_attention import ring_attention_sharded

    devs = np.array(jax.devices()[:4]).reshape(1, 1, 1, 4)
    mesh = Mesh(devs, ("dp", "fsdp", "tp", "sp"))
    q, k, v = _qkv(b=1, h=1, s=128, d=32)
    w = jnp.sin(jnp.arange(32))

    def ring_loss(q, k, v):
        return (ring_attention_sharded(q, k, v, mesh) * w).sum()

    def ref_loss(q, k, v):
        return (_xla_attention(q, k, v) * w).sum()

    with mesh:
        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-5)


def test_gpt_forward_with_ring_attention_matches_single():
    """Full GPT fwd with sp=2 mesh (ring path) == sp=1 (flash/xla path)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init

    cfg = GPTConfig(
        vocab_size=256, seq_len=128, d_model=64, n_layers=2, n_heads=2, dtype="float32"
    )
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 256, jnp.int32)

    ref = gpt_forward(cfg, params, tokens)  # no mesh: dense path

    devs = np.array(jax.devices()[:8]).reshape(2, 1, 2, 2)
    mesh = Mesh(devs, ("dp", "fsdp", "tp", "sp"))
    with mesh:
        out = jax.jit(lambda p, t: gpt_forward(cfg, p, t, mesh))(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)
