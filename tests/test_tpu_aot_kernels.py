"""The main path's Pallas kernels compiled by Mosaic for a v5e at real
widths, WITHOUT the chip: libtpu is installed, and the TPU's compiler
compiles for a chip that is described and not attached.  What interpret mode
cannot show (a slice off the tiling, too much fast memory) fails here and
costs no chip time.  Nothing runs, so nothing here is a number of the device.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU's library, and every xdist
worker imports every test file.  All such tests live in THIS file."""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops import diff_attention as da
from ray_tpu.ops import paged_attention as pa


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("what,blocks,tmax", [
    # phi4-mini-flash-1chip, 32 rows: the ONE shared K/V layer's pool and table
    ("shared_kv", 32 * 144 + 1, 144),
    # one window layer's rings as a pool: 33 slots of 512 / 16 blocks, a fixed table
    ("ring", 33 * 32, 32),
])
def test_differential_pairs_through_the_paged_kernel_compile_for_a_v5e(
        one_chip, monkeypatch, what, blocks, tmax):
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)  # Mosaic, not the interpreter
    rows, heads, kv, e, block = 32, 40, 20, 64, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((blocks, kv // 2, block, 2 * e), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, t, p: da.diff_paged_attention(q, k, v, t, p, kv, impl="pallas")
    ).lower(sds((rows, heads, e), jnp.bfloat16), pool, pool,
            sds((rows, tmax), jnp.int32), sds((rows,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the pools stay where they are: no gathered copy of a table's blocks
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**20


def _custom_call_kernels(text):
    """The Mosaic kernels of a compiled program, by the name each
    ``pallas_call`` was given, sorted, one entry a custom call."""
    calls = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    return sorted(re.search(r"(?:jvp|transpose)?\(?(flash_\w+?)\)*/pallas_call", ln).group(1)
                  for ln in calls)


@pytest.mark.parametrize("batch,heads,seq,head_dim", [
    (26, 16, 1024, 64),  # gpt2m_train: ONE grid cell a head block, the diagonal one
    (1, 16, 4096, 64),   # a 4 x 4 grid: cells below the diagonal run whole, dq^T waits in scratch
    (4, 8, 2048, 128),   # one head of 128 lanes a column block
    (2, 4, 2048, 256),   # a head is one block of 256 lanes: 26 MB of fast memory, asked for
], ids=["gpt2m_train", "seq4096", "width128", "width256"])
def test_flash_kernels_compile_for_a_v5e(one_chip, monkeypatch, batch, heads, seq, head_dim):
    """Forward and the ONE backward kernel through the head-major entry."""
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)  # Mosaic, not the interpreter
    x = jax.ShapeDtypeStruct((batch, heads, seq, head_dim), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x).compile().as_text()
    assert _custom_call_kernels(text) == ["flash_bwd", "flash_fwd"]


@pytest.mark.parametrize("batch,seq,heads,head_dim,blocks", [
    (26, 1024, 16, 64, {}),   # the cell's own call
    (1, 4096, 16, 64, {}),    # several grid blocks
    (1, 4096, 16, 64, dict(block_q_bwd=512, block_k_bwd=1024)),  # unequal: two crossed offsets
    (1, 4096, 8, 128, {}),
    (1, 4096, 4, 256, {}),
], ids=["gpt2m_train", "seq4096", "seq4096_512x1024", "seq4096_width128", "seq4096_width256"])
def test_the_column_blocked_flash_kernels_compile_for_a_v5e(
        one_chip, monkeypatch, batch, seq, heads, head_dim, blocks):
    """gpt2m_train's attention as the train step hands it over: q, k and v
    read out of the projection's own (26, 1024, 3 x 1024) array, two heads of
    64 a 128-lane column block, the gradient ONE (26, 1024, 3072) array that
    the ONE backward kernel writes, dq's, dk's and dv's column blocks by
    copies of its own: nothing head-major, no copy, no ``delta`` in HBM.
    The same at a sequence of several grid blocks and at heads of 128 and
    256 lanes."""
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)  # Mosaic, not the interpreter
    shape = (batch, seq, 3 * heads * head_dim)
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(qkv):
        return fa.flash_attention_packed(qkv, heads, **blocks).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss)).lower(qkv).compile()
    text = compiled.as_text()
    assert _custom_call_kernels(text) == ["flash_bwd", "flash_fwd"]
    assert not re.search(r"\[26,16,1024,64\]|\[416,1024,64\]| copy\(", text)
    # out, dqkv, lse: no second packed array beside the gradient, and no delta
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2 * shape[0] * seq * shape[2] // 3 * 2 * 2


def test_the_train_step_moves_no_head_around_on_a_v5e(one_chip, monkeypatch):
    """``gpt_loss``'s value and gradient at gpt2m_train's widths (batch 26,
    1,024 positions, 16 heads of 64, bf16, ``remat_policy`` "attn"; 2 layers
    suffice under ``lax.scan``) compiled for a v5e: the two flash kernels
    are there, once each a layer body, and NO op of the program has a
    head-major result: the kernels read the fused projection's output and
    write what the next product reads (a head-major copy cost a third of a
    layer outside the kernels, PERF.md section 6, PR 52)."""
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.ops import attention
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)  # Mosaic, not the interpreter
    monkeypatch.setattr(attention, "auto_impl", lambda seq: "flash")  # the rule on a TPU backend
    cfg = GPTConfig(vocab_size=50304, seq_len=1024, d_model=1024, n_layers=2, n_heads=16,
                    dtype="bfloat16", remat_policy="attn", ce_chunks=1)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg)))
    tokens = jax.ShapeDtypeStruct((26, 1025), jnp.int32, sharding=one_chip)
    text = jax.jit(jax.value_and_grad(lambda p, t: gpt_loss(cfg, p, t))).lower(
        params, tokens).compile().as_text()
    assert _custom_call_kernels(text) == ["flash_bwd", "flash_fwd"]
    head_major = [ln.strip()[:160] for ln in text.splitlines()
                  if re.search(r" = \(?\w+\[(1,)?(26,16,1024,64|416,1024,64)\]", ln)]
    assert head_major == []
    # the gradient of the fused projection is one array the one kernel wrote
    assert re.search(r"bf16\[26,1024,3072\][^ ]* custom-call\(.*flash_bwd", text)


def test_gpt2m_train_s_step_program_holds_two_kernels_and_fits_a_v5e(one_chip, monkeypatch):
    """The cell's OWN step program (``make_step_fn`` over ``gpt_loss`` and
    AdamW at the configuration's sizes: 24 layers, batch 26 x 1,025 tokens)
    compiled for a v5e: its Mosaic kernels are exactly ``flash_fwd`` and
    ``flash_bwd``, and XLA's analysis of its memory is not above the 16.38
    GB it gave with two backward kernels and ``delta`` in HBM (PR 52; the
    figure ``gpt2-medium-train.json`` quotes)."""
    import json
    import pathlib

    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.ops import attention
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.parallel.train_step import TrainState, make_step_fn

    monkeypatch.setattr(fa, "_interpret", lambda: False)  # Mosaic, not the interpreter
    monkeypatch.setattr(attention, "auto_impl", lambda seq: "flash")  # the rule on a TPU backend
    conf = json.loads((pathlib.Path(__file__).parents[1]
                       / "benchmark/configs/gpt2-medium-train.json").read_text())
    cfg = GPTConfig(vocab_size=conf["vocab_size"], seq_len=conf["n_positions"],
                    d_model=conf["n_embd"], n_layers=conf["n_layer"], n_heads=conf["n_head"],
                    dtype=conf["dtype"], **conf["model_options"])
    mesh = Mesh([[[list(one_chip.device_set)]]], ("dp", "fsdp", "tp", "sp"))
    opt = optax.adamw(conf["train"]["learning_rate"])
    step = make_step_fn(lambda p, t: gpt_loss(cfg, p, t, mesh), opt, mesh)

    def state():
        params = gpt_init(jax.random.PRNGKey(0), cfg)
        return TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))

    here = NamedSharding(mesh, PartitionSpec())
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=here),
                          jax.eval_shape(state))
    batch = jax.ShapeDtypeStruct((conf["train"]["batch"], cfg.seq_len + 1), jnp.int32, sharding=here)
    compiled = step.lower(shapes, batch).compile()
    assert sorted(set(_custom_call_kernels(compiled.as_text()))) == ["flash_bwd", "flash_fwd"]
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 1e9 <= 16.385


def test_the_latent_decode_kernel_compiles_for_a_v5e(one_chip, monkeypatch):
    """Kimi-K2.5's absorbed decode at the cell's sizes: 16 rows, 64 heads
    over ONE row of 640 lanes a token, 7 layers of 2,049 blocks of 128, a
    table of 138 blocks."""
    from ray_tpu.ops import latent_attention as la

    monkeypatch.setattr(la, "_on_tpu", lambda: True)  # Mosaic, not the interpreter

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, pool, t, p: la.latent_decode_attention(
            q, pool, t, p, rank=512, scale=0.14468, impl="auto")
    ).lower(sds((16, 64, 640), jnp.bfloat16), sds((7 * 2049, 128, 640), jnp.bfloat16),
            sds((16, 138), jnp.int32), sds((16,), jnp.int32)).compile()
    assert "latent_attention_decode" in compiled.as_text()
    # the pool stays where it is: no gathered copy of a table's blocks
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**20


def test_the_latent_chunk_kernel_compiles_for_a_v5e(one_chip, monkeypatch):
    """Kimi-K2.5's prefill chunk at the cell's sizes: 512 queries of 64
    heads x (128 + 64) against a table of 138 blocks of 128 latent rows,
    four heads a grid step, key tiles of 768 walked in sub-tiles of 384,
    the statistics replicated over 128 lanes (what interpret mode at the
    tests' widths never builds)."""
    from ray_tpu.ops import latent_attention as la

    monkeypatch.setattr(la, "_on_tpu", lambda: True)  # Mosaic, not the interpreter

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert (la._key_tile(138 * 128, False), la._sub_tile(768, False)) == (768, 384)
    compiled = jax.jit(
        lambda qn, qr, pool, t, wk, wv: la.latent_chunk_attention(
            qn, qr, pool, t, 16384 + jnp.arange(512, dtype=jnp.int32), wk, wv,
            rank=512, scale=0.14468, impl="auto")
    ).lower(sds((512, 64, 128), jnp.bfloat16), sds((512, 64, 64), jnp.bfloat16),
            sds((7 * 2049, 128, 640), jnp.bfloat16), sds((138,), jnp.int32),
            sds((512, 64, 128), jnp.bfloat16), sds((512, 64, 128), jnp.bfloat16)).compile()
    assert "latent_attention_chunk" in compiled.as_text()
    # keys and values expanded once (2 x 17,664 x 8,192 bf16) and the gathered
    # rows; no float32 score array of the table's width beside them
    assert compiled.memory_analysis().temp_size_in_bytes < 700 * 2**20


@pytest.mark.parametrize("rows", [16, 512], ids=["decode", "chunk"])
@pytest.mark.parametrize("d,f,held,layers,top_k,m", [
    (7168, 2048, 12, 6, 8, 3),     # kimi-k2.5-ep32-l7-1chip: experts of 88 MB
    (4096, 768, 36, 10, 10, 3),    # granite-4.0-h-small-ep2-l10-1chip: 360 experts of 18.9 MB
    (2048, 1536, 64, 8, 4, 3),     # lfm2-24b-a2b-l10-1chip: 512 experts of 18.9 MB, a layer's WHOLE
    # nemotron-3-nano-30b-ep8-1chip: 368 UNGATED experts (two matrices), the
    # published f = 1,856 = 14.5 lane rows STORED as 15 (1,920: 20.6 MB)
    (2688, 1920, 16, 23, 6, 2),
], ids=["kimi", "granite_h", "lfm2_moe", "nemotron_h"])
def test_the_expert_layer_reads_no_copy_of_its_experts_on_a_v5e(
        one_chip, monkeypatch, d, f, held, layers, top_k, m, rows):
    """Both forms index the experts of every layer where they lie: the expert
    layer at the published widths holds no temporary the size of an expert,
    let alone of a layer's held ones (the smallest layer here holds 679 MB).
    A decode's 16 rows go through the batch form's ONE kernel, a chunk's 512
    through the grouped form's ONE kernel (nothing in expert order is written
    out beside it: the ordering is 512 x ``top_k`` integers and weights); the
    weight blocks lie inside the module's VMEM budget, and what a kernel asks
    for in all -- the compiler refuses one past its ``vmem_limit_bytes`` --
    inside the 128 MiB a v5e has."""
    import re

    from ray_tpu.ops import moe

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)  # Mosaic, not the interpreter

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(x, mask, wmat, first, *weights):
        return moe.expert_layer(x, mask, wmat, *weights, first=first, top_k=top_k)

    n = held * layers
    compiled = jax.jit(layer).lower(
        sds((rows, d), jnp.bfloat16), sds((rows, held), jnp.bool_),
        sds((rows, held), jnp.float32), sds((), jnp.int32),
        *[sds((n, d, f), jnp.bfloat16)] * (m - 1), sds((n, f, d), jnp.bfloat16)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20 < m * d * f * 2
    text = compiled.as_text()
    name = "moe_grouped_experts" if rows > moe.TILE else "moe_batch_experts"
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and name in calls[0]
    # granite's expert goes whole, kimi's in quarters of f; two buffers of the
    # matrices' blocks (three of a gated expert, two of an ungated one) are
    # what the budget is stated for
    bf = moe.block_f(d, f, 2, matrices=m)
    assert bf == {768: 768, 2048: 512, 1536: 1536, 1920: 1920}[f]
    assert 2 * m * d * bf * 2 <= moe.VMEM_BUDGET
    # ... and all the kernel's VMEM (its scoped limit): the blocks, the rows
    # and the accumulator (a chunk's: 512 x d float32 each) and the rest
    limit = int(re.search(r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
                          r'"size":"(\d+)"', calls[0]).group(1))
    assert 2 * m * d * bf * 2 < limit < 128 * 2**20


def test_an_expert_width_off_the_lane_rows_is_copied_whole_on_a_v5e(one_chip, monkeypatch):
    """Why Nemotron's experts are STORED at 1,920: Mosaic takes a block whose
    last axis is 1,856 (= 14.5 x 128), but XLA lays such an array out in whole
    lane rows for it first: a padded copy of every layer's ``W_up`` (3.8 GB)
    stands among the temporaries of ONE expert layer."""
    from ray_tpu.ops import moe

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)  # Mosaic, not the interpreter
    d, f, n = 2688, 1856, 16 * 23

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda x, mask, wmat, up, down: moe.expert_layer(
        x, mask, wmat, up, down, top_k=6)).lower(
        sds((16, d), jnp.bfloat16), sds((16, 16), jnp.bool_), sds((16, 16), jnp.float32),
        sds((n, d, f), jnp.bfloat16), sds((n, f, d), jnp.bfloat16)).compile()
    assert "moe_batch_experts" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes >= n * d * 1920 * 2


@pytest.mark.parametrize("h,p,n,slots,hb", [
    (32, 128, 256, 34, 8),        # falcon-h1-34b-l8-1chip
    (128, 64, 128, 9 * 17, 32),   # granite-4.0-h-small-ep2-l10-1chip: half a vreg's lanes a row
    (64, 64, 128, 23 * 17, 32),   # nemotron-3-nano-30b-ep8-1chip: 23 layers' slots, 8 groups
], ids=["falcon_h1", "granite_h", "nemotron_h"])
def test_the_ssd_decode_kernel_compiles_for_a_v5e_and_updates_in_place(
        one_chip, h, p, n, slots, hb):
    """The Mamba-2 update at each cell's sizes: 16 rows, ``h`` heads of ``p x
    n`` float32, 1 MB of heads a grid step.  The pool is aliased and no copy
    of it stands among the temporaries."""
    from ray_tpu.ops import ssd

    s = 16
    assert ssd.heads_per_block(h, p, n) == hb

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda state, *rest: ssd._decode_core_pallas(state, *rest, interpret=False),
        donate_argnums=(0,),
    ).lower(sds((slots, h, p, n)), sds((s, h, p)), sds((s, h)), sds((s, h, n)),
            sds((s, h, n)), sds((s,), jnp.int32), sds((s,), jnp.bool_)).compile()
    mem, pool = compiled.memory_analysis(), slots * h * p * n * 4
    assert "tpu_custom_call" in compiled.as_text()
    assert mem.alias_size_in_bytes >= pool and mem.temp_size_in_bytes < pool // 16


def test_the_retention_decode_kernel_compiles_for_a_v5e_and_updates_in_place(one_chip):
    """Brumby's decode at the cell's sizes: 16 rows, 8 key-value heads of 5
    query heads, a state of (136, 8320) float32 = 4.5 MB copied into VMEM and
    back by the kernel's own DMAs (two states of scratch).  The pool is
    aliased and no copy of it stands among the temporaries; ``y`` leaves as
    (G, VD) rows: no array of (VD, G) columns is made or turned."""
    from ray_tpu.ops import power_retention as pr

    s, h, g, slots = 16, 8, 5, 17
    vd, f = pr.state_dims(128)
    assert pr._row_blocks(vd // 8) == [(0, 4), (4, 4), (8, 4), (12, 5)]

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda state, *rest: pr._decode_core_pallas(state, *rest, interpret=False),
        donate_argnums=(0,),
    ).lower(sds((slots, h, vd, f)), sds((s, h * g, f)), sds((s, h, f)), sds((s, h, vd)),
            sds((s, h)), sds((s,), jnp.int32), sds((s,), jnp.bool_)).compile()
    mem, pool, text = compiled.memory_analysis(), slots * h * vd * f * 4, compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"f32[{s},{h},{g},{vd}]" in text and f"f32[{s},{h},{vd},{g}]" not in text
    assert mem.alias_size_in_bytes >= pool and mem.temp_size_in_bytes < pool // 16


@pytest.mark.parametrize("heads,kv,blocks,tmax,e", [
    (20, 4, 8 * 1601, 100, 128),  # falcon-h1-34b-l8-1chip: w = 5 over 8 layers of 1,601 blocks
    (32, 8, 225, 14, 128),        # granite-4.0-h-small-ep2-l10-1chip: w = 4 over ONE layer
    # lfm2-24b-a2b-l10-1chip: 8 heads of 64 as 4 rows of 128 lanes, w = 8 over 2 layers
    (32, 8, 2 * 1601, 100, 64),
    (32, 2, 6 * 1185, 74, 128),   # nemotron-3-nano-30b-ep8-1chip: w = 16 over a row of TWO heads
], ids=["falcon_h1", "granite_h", "lfm2_moe", "nemotron_h"])
def test_grouped_query_heads_through_the_paged_kernel_compile_for_a_v5e(
        one_chip, monkeypatch, heads, kv, blocks, tmax, e):
    """The decode attention of the grouped-query families: the query heads of
    a key-value head of 128 ride the window axis over blocks of 128; heads of
    64 lie two a row, unpadded, and a row's 8 query heads ride it."""
    from ray_tpu.ops import gqa_attention as ga

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)  # Mosaic, not the interpreter

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((blocks, kv * e // 128, 128, 128), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, t, p: ga.gqa_paged_attention(q, k, v, t, p, impl="pallas")
    ).lower(sds((16, heads, e), jnp.bfloat16 if e == 128 else jnp.float32), pool, pool,
            sds((16, tmax), jnp.int32), sds((16,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**20


@pytest.mark.parametrize("layers,blocks,window", [
    (1, 16 * 262 + 1, None),   # trinity-large-ep8-l5-1chip: the ONE full layer's sub-pool
    (4, 16 * 37 + 1, 4096),    # its four window layers': the walk starts at the row's first block
], ids=["full", "window"])
def test_a_windowed_walk_through_the_paged_kernel_compiles_for_a_v5e(
        one_chip, monkeypatch, layers, blocks, window):
    """Trinity's decode attention: 48 query heads on 8 key-value heads of 128
    (w = 6) over a table of 262 blocks; a window layer's kernel reads its
    lower bound from the positions, as it reads its length."""
    from ray_tpu.ops import gqa_attention as ga

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)  # Mosaic, not the interpreter

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((layers * blocks, 8, 128, 128), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, t, p: ga.gqa_paged_attention(q, k, v, t, p, impl="pallas", window=window)
    ).lower(sds((16, 48, 128), jnp.bfloat16), pool, pool,
            sds((16, 262), jnp.int32), sds((16,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**20


def _padded_bytes(dims, layout, itemsize):
    """The bytes of an array of ``dims`` in a compiled program's ``layout``
    (``minor to major:T(tile)...``): the tile's axes padded to whole tiles."""
    order, _, tiles = layout.partition(":")
    phys = [dims[int(i)] for i in order.split(",")][::-1]          # major to minor
    tile = re.match(r"T\(([\d,]+)\)", tiles)
    tile = [int(v) for v in tile.group(1).split(",")] if tile else []
    for at, t in enumerate(tile, len(phys) - len(tile)):
        phys[at] = -(-phys[at] // t) * t
    return math.prod(phys) * itemsize


def test_a_chunk_s_mamba_runs_carry_the_conv_tails_in_their_own_bytes_on_a_v5e(one_chip):
    """Runs of Mamba-2 chunk steps at Nemotron-3's mixer widths, each run one
    ``_carry_loop`` over the tails and the states with a plain op before the
    next, as ``pattern_layers`` makes a prefill's.  A leaf of ``(3 taps,
    conv_dim)`` a slot rides such loops with the 3 in the LANES, 128 / 3
    times the pool, and is re-laid between them; a slot's tail as ONE row
    leaves no axis of 3 to choose.  No form of the pool in the compiled text
    passes four times its own bytes (the stored ``(layers, 17, row)`` pads 17
    to 24)."""
    from ray_tpu.llm.model_runner import _carry_loop
    from ray_tpu.models.blocks import Mamba2, rmsnorm

    runs, d, chunk, slots = (2, 1, 2, 1, 1), 2688, 512, 17
    mixer = Mamba2(d_ssm=4096, heads=64, d_state=128, n_groups=8, d_conv=4, eps=1e-5,
                   dtype=jnp.dtype("bfloat16"), sub=128, impl="pallas")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    keys = tuple(jax.random.split(jax.random.PRNGKey(0), 6))
    stacks = [jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda n=n: mixer.init(keys, n, d, d**-0.5, 4096**-0.5, (1.0, 16.0), (0.001, 0.1))))
        for n in runs]
    leaves = mixer.state_leaves(sum(runs), "float32")
    pools = [sds((n, slots, *shape), dt) for n, shape, dt in leaves.values()]

    def step(stacks, x, conv, ssd, at, fresh, n_valid):
        valid, first = jnp.arange(chunk) < n_valid, 0
        for n, stack in zip(runs, stacks):

            def layer_fn(h, layer, conv, ssd, base, first=first):
                y, conv, ssd = mixer.chunk(
                    h, layer, conv, ssd, first * slots + base + at, fresh, n_valid, valid)
                return h + y, conv, ssd

            x, conv, ssd = _carry_loop(stack, x, (conv, ssd), layer_fn)
            x, first = rmsnorm(x, jnp.ones((d,)), 1e-5), first + n
        return x, conv, ssd

    compiled = jax.jit(step, donate_argnums=(2, 3)).lower(
        stacks, sds((chunk, d), "float32"), *pools,
        sds((), "int32"), sds((), "bool"), sds((), "int32")).compile()
    count = sum(runs) * slots * math.prod(leaves["conv"][1])
    forms = {}  # every form of the pool in the text -> its bytes
    for m in re.finditer(r"bf16\[([\d,]+)\]\{([^}]*)\}", compiled.as_text()):
        dims = [int(v) for v in m.group(1).split(",")]
        if math.prod(dims) == count:
            forms[m.group(0)] = _padded_bytes(dims, m.group(2), 2)
    assert forms and max(forms.values()) <= 4 * count * 2, forms
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
