"""A DROPLESS expert layer over the experts ONE chip holds of a layer that
is spread over many (expert parallelism, this chip's share: 12 of 384 held,
Kimi-K2.5; 36 of 72, Granite-4.0-H), or ALL of it (``offset`` 0, ``held`` the
router's whole width: LFM2's 64 of 64, where nothing is left out and the
"share" below is the layer).

The router scores every token over ALL of the layer's experts and chooses
``top_k`` of them (``route``: sigmoid scores and a selection bias;
``route_logits``: the largest logits, a softmax over the chosen; a family
calls the one its model has); this chip holds experts ``offset .. offset +
held`` and computes THEIR part of the result: ``sum over chosen AND held e
of w_e * Expert_e(x)``.  What the absent experts would add is left out (it
is the other chips' part); nothing here stands in for them.

An expert is one of TWO forms, and its weights, not a flag, choose
(``expert_mlp``): three matrices ``(gate, up, down)`` are the gated SiLU form
``(silu(x W_gate) . (x W_up)) W_down`` (``swiglu``: Kimi-K2.5, Granite-4.0-H,
LFM2), two ``(up, down)`` the UNGATED ``relu(x W_up)**2 W_down`` (``relu2``:
Nemotron-3-Nano).  ``expert_layer`` takes the matrices there are, and its two
kernels, their block specs, ``block_f`` and the VMEM they ask for count them;
below, "swiglu" stands for either.

No capacity and no drop: every (token, held expert) pair the router chose is
computed, whatever the load's shape (``models.gpt._moe_mlp``, the training
path's layer, drops what exceeds a fixed capacity and so matches no
reference; this one does).  Shapes stay static all the same, and an expert
no row chose is never read.  Both of ``expert_layer``'s forms visit a
compacted list of the touched experts, made once a layer from ``load > 0``
(``touched``), and on a TPU each is ONE Pallas kernel a layer (the pattern of
``ops.ssd``): the list is scalar prefetch, a weight block's index is ``first +
ids[i]`` in the flat arrays of every layer's experts, the grid is the static
``(experts held, blocks of f)``; a step past the list's end names the block
before it (nothing is fetched) and computes nothing (a layer whose list is
EMPTY still names one block of its first expert: the one read no row asked
for).  Pallas' double buffering has expert ``i + 1``'s weights in flight
while expert ``i`` multiplies, which a loop of XLA dots on dynamically
indexed operands does not do: an expert of 18.9 MB is 23 us of reads, 37-45
through such a loop and 25 through either kernel (PR 59, 60, 62).  An
expert's weights are read ONCE a layer however many pairs it has.  The
batch's rows ``n`` against ``tile`` choose the form -- the shape, no family's
name, no option:

* ``n <= tile`` (every decode: 16 rows), the BATCH FORM
  (``moe_batch_experts``): a touched expert sees ALL ``n`` rows and the
  router's weight column does the selecting, ``out = sum over touched e, in
  expert order, of where(mask[:, e], wmat[:, e], 0)[:, None] * swiglu(x,
  W_e)``: no ordering, no gather, no scatter; ``x`` stays where it is and
  ``out`` is an accumulator that is never indexed.  A row gets an exact
  ``+0.0`` from an expert it did not choose, so its result does not depend on
  what the other rows chose.  The compacted list is what keeps the static grid
  honest where 16 rows x 4 touch 41 of 64 held experts (LFM2): a third of the
  grid's steps fetch nothing.
* ``n > tile`` (a prefill chunk: 512 rows, 32 pairs an expert of LFM2's 64,
  71 of Granite-4.0-H's 36), the GROUPED FORM (``moe_grouped_experts``): a
  touched expert sees ITS pairs, all of them, in one grid step.  The pairs
  are put in expert order ONCE a layer (``expert_order``: running counts and
  comparisons, no sort, no scatter; the static bound is ``n x min(top_k,
  held)`` places, so ``top_k`` is a shape fact the callers pass) and the
  order is scalar prefetch too: every row of the batch lies in VMEM (float32,
  copied in once), the step copies its expert's rows out of it by token, row
  by row, into blocks of ``row_block(n)`` rows (the MXU's 128; as many blocks
  as the expert has pairs: a traced bound), runs ``swiglu`` on each block,
  and adds each result row, times the router's weight, to ITS row of an (n,
  d) float32 accumulator that lies in VMEM through the whole grid and leaves
  it once.  So the un-sort is the kernel's own: nothing in expert order is
  ever written to HBM, and since experts come in order a row's sum is added
  in expert order, the batch form's sum and (at whole ``f``) bit for bit
  what a tile loop of XLA dots gave (PR 62, on the chip).

Elsewhere (``impl="xla"``, and ``auto`` off a TPU) each form is a
``fori_loop`` of plain ``jax.numpy`` over the same list (the grouped one
gathers a block's rows and scatter-adds its results: what the tile loop of
PR 45-61 did a tile of 64, without its sort and its search).

The kernels' weight blocks are cut along ``f`` alone (``block_f``): the
widest multiple of 128 lanes that divides ``f`` and keeps two buffers of the
expert's matrices' blocks inside ``VMEM_BUDGET`` (all of ``f`` where that
fits: Nemotron's 2 x 2688 x 1920 goes whole; its published 1,856 is STORED in
whole lane rows, since XLA copies an array whose last axis is off them into a
padded layout before a kernel reads it).  An expert of 3 x 4096 x 768
bfloat16 (18.9 MB) goes whole, every product one dot and every read
contiguous; one of 3 x 7168 x 2048 (88 MB, Kimi-K2.5) goes in blocks of
``f``, and a row's partial sums of the down product are added to the
accumulator block by block, in float32, in both forms.

What a family's step programs COUNT of the layer on the device is here too,
once for every family (``COUNTERS``, ``counters_shape``, ``count_routed``,
``count_step``, ``read_counters``, below ``grouped_steps``): a body calls its
router and ``held_pairs`` and hands the mask to ``count_routed``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.paged_attention import _on_tpu

#: the rows that choose ``expert_layer``'s form: a batch of no more takes the
#: batch form (every touched expert sees all of them), a larger one the
#: grouped form (every touched expert sees its own pairs)
TILE = 64
#: rows of an expert's pairs one product of the grouped form takes: the MXU's
#: height (on a v5e blocks of 32, 64 and 128 rows read alike, PR 62: a touched
#: expert's step is its weights' reads; a taller block only adds padding)
BLOCK_ROWS = 128
#: VMEM the kernels' weight blocks may take: two buffers of an expert's
#: matrices' blocks (a v5e has 128 MiB; the batch kernel's ``x`` and
#: accumulator are under 2 MB beside them, the grouped kernel's rows,
#: accumulator and one block of each 8 x (512 + 128) x d bytes: 37 MB at
#: Kimi-K2.5's 7168)
VMEM_BUDGET = 64 << 20


def route(x32, router_kernel, select_bias, top_k: int, scaling: float, eps: float = 1e-20):
    """Sigmoid scores over every expert, ``top_k`` chosen by ``score +
    select_bias`` (the bias chooses and does not weigh), weights the chosen
    scores over their sum plus ``eps`` (1e-20 is DeepSeek-V3's and
    Kimi-K2.5's; LFM2 publishes 1e-6), times ``scaling``.  All float32, the
    product at ``highest`` precision: the choice is discontinuous in the scores and
    the 8th and 9th lie about 0.05 apart.  x32: (N, d) float32.  Returns
    (chosen (N, top_k) int32, weights (N, top_k) float32)."""
    z = jnp.dot(x32, router_kernel.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.sigmoid(z)
    _, chosen = jax.lax.top_k(p + select_bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    return chosen, picked / (picked.sum(-1, keepdims=True) + eps) * scaling


def route_logits(x32, router_kernel, top_k: int):
    """The second router (Granite-4.0-H's): the ``top_k`` largest LOGITS over
    every expert chosen, weights a softmax over the chosen logits alone: no
    sigmoid, no selection bias, no scaling factor.  All float32, the product
    at ``highest`` precision.  x32: (N, d) float32.  Returns (chosen (N,
    top_k) int32, weights (N, top_k) float32, summing to 1)."""
    z = jnp.dot(x32, router_kernel.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    picked, chosen = jax.lax.top_k(z, top_k)
    return chosen, jax.nn.softmax(picked, axis=-1)


def held_pairs(chosen, weights, offset: int, held: int, live):
    """The router's choice as this chip sees it: (mask (N, held) bool, the
    token chose held expert e; wmat (N, held) float32, with what weight).
    A row that is not ``live`` (a dead decode row, a chunk's padding) has no
    pair."""
    local = chosen[:, :, None] - offset == jnp.arange(held)[None, None, :]
    local = local & live[:, None, None]
    return local.any(axis=1), (local * weights[:, :, None]).sum(axis=1)


def _dot(a, k):
    return jnp.dot(a, k.astype(a.dtype), preferred_element_type=jnp.float32)


def swiglu(x, gate, up, down):
    """``(silu(x gate) * (x up)) down``: products on x's dtype, sums, the
    activation and the result in float32."""
    return _dot((jax.nn.silu(_dot(x, gate)) * _dot(x, up)).astype(x.dtype), down)


def relu2(x, up, down):
    """``relu(x up)**2 down``, the UNGATED expert: products on x's dtype,
    sums, the activation and the result in float32."""
    return _dot(jnp.square(jax.nn.relu(_dot(x, up))).astype(x.dtype), down)


def expert_mlp(x, *weights):
    """An expert's function, as its WEIGHTS say: three matrices ``(gate, up,
    down)`` are ``swiglu``, two ``(up, down)`` are ``relu2``.  Every form of
    ``expert_layer`` calls this and nothing else of an expert."""
    return swiglu(x, *weights) if len(weights) == 3 else relu2(x, *weights)


def row_block(n: int) -> int:
    """Rows of an expert's pairs one product of the grouped form takes: the
    MXU's height, or all ``n`` rows (in whole sublane tiles) where a batch has
    fewer."""
    return min(BLOCK_ROWS, -(-n // 16) * 16)


def tile_rows(load, n: int, tile: int = TILE):
    """The rows ``expert_layer`` computes for a ``load`` (pairs by held
    expert) over ``n`` rows, in the form that runs: the batch form gives a
    touched expert all ``n`` rows, the grouped form its pairs in whole blocks
    of ``row_block(n)``."""
    rows = n if n <= tile else row_block(n)
    return ((load + (rows - 1)) // rows).sum().astype(jnp.int32) * rows


def batch_steps(load, n: int, tile: int = TILE):
    """The expert steps the BATCH FORM makes for a ``load`` over ``n`` rows:
    one a touched expert where ``n <= tile``; none where the grouped form
    runs."""
    return (load > 0).sum().astype(jnp.int32) * int(n <= tile)


def grouped_steps(load, n: int, tile: int = TILE):
    """The expert steps the GROUPED FORM makes: one a touched expert where
    ``n > tile``; none where the batch form runs."""
    return (load > 0).sum().astype(jnp.int32) * int(n > tile)


# -- the routed layer's ledger ------------------------------------------------
#
# What the step programs of a family with an expert layer count ON THE DEVICE
# (the router's load is known nowhere else): one int32 array ``(1,
# len(COUNTERS) + held)`` rides every step beside the pools, the scalar
# counters first, then the pairs by held expert.  ``stats()["moe"]`` is its
# reading.  One more counter is one name here and one line in
# ``count_routed``.

#: ``stats()["moe"]``: the scalar counters, then ``load`` (one a held expert)
COUNTERS = ("decode_pairs", "decode_touched", "decodes", "chunk_pairs", "chunks",
            "decode_tile_rows", "decode_expert_steps", "chunk_touched", "chunk_tile_rows",
            "chunk_expert_steps")


def counters_shape(held: int) -> tuple:
    """Shapes and dtypes of what the steps carry beside the pools."""
    return (jax.ShapeDtypeStruct((1, len(COUNTERS) + held), jnp.int32),)


def count_routed(counts, mask, phase: str):
    """One expert layer of a ``decode`` or a ``chunk`` into the ledger
    (``counts``: its flat view): the load by held expert and, under
    ``<phase>_*``, the layer's pairs, its touched experts (what the step reads
    of the held weights), the rows ``expert_layer`` computes for them
    (``tile_rows``) and the expert steps of the form that runs (a decode's are
    the batch form's, a chunk's the grouped form's: none where the other form
    ran).  ``mask``: ``held_pairs``', so a dead row counts nowhere."""
    n = mask.shape[0]
    load = mask.sum(axis=0).astype(jnp.int32)
    counts = counts.at[len(COUNTERS):].add(load)
    for name, value in (("pairs", load.sum()), ("touched", (load > 0).sum()),
                        ("tile_rows", tile_rows(load, n))):
        counts = counts.at[COUNTERS.index(f"{phase}_{name}")].add(value.astype(jnp.int32))
    steps = batch_steps if phase == "decode" else grouped_steps
    return counts.at[COUNTERS.index(f"{phase}_expert_steps")].add(steps(load, n))


def count_step(counts, phase: str):
    """One more ``decode`` or ``chunk`` (``counts``: the array as it rides)."""
    return counts.at[0, COUNTERS.index(f"{phase}s")].add(1)


def read_counters(arrays) -> dict:
    """``stats()``'s part from the fetched ledger: ``{"moe": {name: count,
    ..., "load": [pairs by held expert]}}``."""
    flat = np.asarray(arrays[0]).reshape(-1)
    out = {name: int(flat[i]) for i, name in enumerate(COUNTERS)}
    out["load"] = [int(x) for x in flat[len(COUNTERS):]]
    return {"moe": out}


def touched(mask):
    """The experts some row chose, compacted, in expert order: (ids (E,)
    int32, how many).  Entries past the count repeat the last touched expert
    (0 where there is none), so a kernel's step there names the block it
    already holds.  A comparison of a running count: no sort, no scatter."""
    experts = mask.shape[1]
    hit = mask.any(axis=0)
    upto = jnp.cumsum(hit.astype(jnp.int32))
    at = jnp.arange(experts, dtype=jnp.int32)
    ids = (upto[None, :] <= at[:, None]).sum(axis=1).astype(jnp.int32)
    return jnp.minimum(ids, jnp.where(hit, at, 0).max()), upto[-1]


def block_f(d: int, f: int, itemsize: int, budget: int = VMEM_BUDGET, matrices: int = 3) -> int:
    """Columns of ``f`` a grid step of the kernels holds of each matrix: all
    of ``f`` where two buffers of an expert's ``matrices`` ``d x f`` matrices
    fit the budget, else the widest multiple of 128 that divides ``f`` and
    does (an ``f`` no 128 divides, 1,856, has no such cut: it goes whole or
    not at all)."""
    fits = lambda bf: 2 * matrices * d * bf * itemsize <= budget  # noqa: E731
    if fits(f):
        return f
    cuts = [bf for bf in range(128, f, 128) if f % bf == 0 and fits(bf)]
    if not cuts:
        raise ValueError(f"no block of an expert of {d} x {f} fits {budget} bytes of VMEM")
    return cuts[-1]


def _batch_xla(x, wsel, ids, count, weights, first):
    """The batch form in plain ``jax.numpy``: one ``expert_mlp`` of ALL rows a
    touched expert, weighed by its column ``wsel[e]`` (E, N)."""
    at = lambda k, e: jax.lax.dynamic_index_in_dim(  # noqa: E731
        k, first + e, 0, keepdims=False)

    def one_expert(i, out):
        e = ids[i]
        y = expert_mlp(x, *(at(k, e) for k in weights))
        return out + jax.lax.dynamic_index_in_dim(wsel, e, 0, keepdims=False)[:, None] * y

    return jax.lax.fori_loop(0, count, one_expert, jnp.zeros(x.shape, jnp.float32))


def _batch_kernel(ids_ref, meta_ref, x_ref, w_ref, *refs):
    """One (touched expert, block of ``f``): ``x_ref`` (N, d) and ``o_ref``
    (N, d) float32 (the last of ``refs``) stay in VMEM through the whole grid;
    ``w_ref`` (1, N, 1) is the expert's weight column, the other ``refs`` its
    matrices' blocks: ``gate`` (where it has one) and ``up`` (1, d, bf),
    ``down`` (1, bf, d).  ``expert_mlp`` on the block: either activation is
    elementwise in ``f``, the down product's partial sums add in float32."""
    from jax.experimental import pallas as pl

    *weight_refs, o_ref = refs
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(i < meta_ref[0])
    def _():
        o_ref[...] += w_ref[0] * expert_mlp(x_ref[...], *(r[0] for r in weight_refs))


def _weight_specs(d: int, bf: int, steps: int, matrices: int):
    """The block specs of an expert's ``matrices`` matrices (the last is
    ``down``, the others read ``x``'s columns) over a grid of (listed
    expert ``i``, block ``j`` of ``f``) whose first two scalar-prefetch
    operands are the compacted list ``ids`` and ``meta = (count, first)``:
    expert ``ids[i]`` of this layer; a step past the list's end names the
    block the step before it held."""
    from jax.experimental import pallas as pl

    def place(i, j, ids, meta):
        return meta[1] + ids[i], jnp.where(i < meta[0], j, steps - 1)

    def columns(i, j, ids, meta, *_):
        e, b = place(i, j, ids, meta)
        return e, 0, b

    def rows_of(i, j, ids, meta, *_):
        e, b = place(i, j, ids, meta)
        return e, b, 0

    return [*(pl.BlockSpec((1, d, bf), columns) for _ in range(matrices - 1)),
            pl.BlockSpec((1, bf, d), rows_of)]


def _batch_pallas(x, wsel, ids, count, weights, first, *, interpret: bool,
                  bf: int | None = None):
    """The batch form as ONE kernel over the compacted list.  Rows are padded
    to whole sublane tiles of ``x``'s dtype (16 rows of bfloat16 are one)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (n, d), experts, f, m = x.shape, wsel.shape[0], weights[0].shape[-1], len(weights)
    size = weights[0].dtype.itemsize
    bf = bf or block_f(d, f, size, matrices=m)
    pad = -n % (32 // x.dtype.itemsize)
    x = jnp.pad(x, ((0, pad), (0, 0)))
    w = jnp.pad(wsel, ((0, 0), (0, pad)))[:, :, None]
    rows = n + pad
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(experts, f // bf),
        in_specs=[
            pl.BlockSpec((rows, d), lambda i, j, ids, meta: (0, 0)),
            pl.BlockSpec((1, rows, 1), lambda i, j, ids, meta: (ids[i], 0, 0)),
            *_weight_specs(d, bf, f // bf, m),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda i, j, ids, meta: (0, 0)),
    )
    out = pl.pallas_call(
        _batch_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the matrices' blocks, double-buffered, and the rest
            vmem_limit_bytes=2 * m * d * bf * size + (16 << 20),
        ),
        interpret=interpret,
        name="moe_batch_experts",
    )(ids, jnp.stack([count, jnp.asarray(first, jnp.int32)]), x, w, *weights)
    return out[:n]


def expert_order(mask, wmat, top_k: int | None):
    """The pairs of ``mask`` (N, E) in EXPERT ORDER, made once a layer from
    running counts and comparisons (no sort, no scatter): expert ``e``'s pairs
    are the places ``starts[e] .. starts[e] + counts[e]``, in token order.
    Returns ``(starts (E,), counts (E,), token (P,): the row a place's pair
    belongs to, weight (P,): the router's for it)``, ``P = N x min(top_k, E)``
    the static bound (no ``top_k``: a row may choose every expert); places
    past the pairs hold row 0 at weight 0.  A row's pairs past ``top_k``
    would have no place: it is the router's."""
    n, experts = mask.shape
    k = min(top_k or experts, experts)
    hits = mask.astype(jnp.int32)
    counts = hits.sum(axis=0)
    starts = jnp.cumsum(counts) - counts
    place = starts[None, :] + jnp.cumsum(hits, axis=0) - 1            # (N, E)
    # a row's pairs counted along the experts: its j-th is where that count is
    # j, which brings (N, E) down to the (N, k) that can hold a pair
    pick = mask[:, :, None] & ((jnp.cumsum(hits, axis=1) - 1)[:, :, None]
                               == jnp.arange(k, dtype=jnp.int32))     # (N, E, k)
    # (N k,): where each of a row's pairs lies, -1 where it has no j-th
    place = (jnp.where(pick, place[:, :, None] + 1, 0).sum(axis=1) - 1).reshape(-1)
    weight = jnp.where(pick, wmat[:, :, None], 0.0).sum(axis=1).reshape(-1)
    # the inverse: place p holds the pair that lies at p
    at = jnp.arange(n * k, dtype=jnp.int32)
    here = place[None, :] == at[:, None]
    token = jnp.where(here, (at // k)[None, :], 0).sum(axis=1)
    return starts, counts, token, jnp.where(here, weight[None, :], 0.0).sum(axis=1)


def _grouped_xla(x, ids, count, starts, counts, token, weight, weights, first):
    """The grouped form in plain ``jax.numpy``: a touched expert's pairs go
    through its weights a block of rows at a time, gathered by ``token`` and
    added back to their rows (a block's places past the expert's pairs are
    dropped)."""
    n, rb = x.shape[0], row_block(x.shape[0])
    at = lambda k, e: jax.lax.dynamic_index_in_dim(  # noqa: E731
        k, first + e, 0, keepdims=False)
    token, weight = jnp.pad(token, (0, rb)), jnp.pad(weight, (0, rb))

    def one_expert(i, out):
        e = ids[i]
        mine = [at(k, e) for k in weights]

        def one_block(b, out):
            row0 = starts[e] + b * rb
            valid = b * rb + jnp.arange(rb, dtype=jnp.int32) < counts[e]
            rows = jax.lax.dynamic_slice_in_dim(token, row0, rb)
            w = jax.lax.dynamic_slice_in_dim(weight, row0, rb)
            y = expert_mlp(x[rows], *mine)
            return out.at[jnp.where(valid, rows, n)].add(y * w[:, None], mode="drop")

        return jax.lax.fori_loop(0, (counts[e] + (rb - 1)) // rb, one_block, out)

    return jax.lax.fori_loop(0, count, one_expert, jnp.zeros(x.shape, jnp.float32))


def _grouped_kernel(ids_ref, meta_ref, start_ref, count_ref, token_ref, weight_ref,
                    x_hbm, *refs, dtype):
    """One (touched expert, block of ``f``).  ``x_all`` (N, d) float32, every
    row of the batch, comes into VMEM once and ``out`` (N, d) float32, the
    accumulator, leaves it once: neither is double-buffered.  The expert's
    pairs go through the weight blocks ``x_blk``'s rows at a time (a traced
    bound: as many blocks as it has pairs): their rows are copied out of
    ``x_all`` by ``token``, row by row, ``expert_mlp`` runs on the block in
    the products' ``dtype``, and each result row is added to ITS row of ``out`` times the
    router's weight -- experts come in order and a block of ``f`` after its
    predecessor, so a row's sum is the batch kernel's.  Rows of a block past
    the pairs hold what the block held before; theirs are results nobody
    takes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    *weight_refs, o_hbm, x_all, out, x_blk, y_blk, sem = refs
    i, j, rb = pl.program_id(0), pl.program_id(1), x_blk.shape[0]

    @pl.when((i == 0) & (j == 0))
    def _():
        copy = pltpu.make_async_copy(x_hbm, x_all, sem.at[0])
        copy.start()
        out[...] = jnp.zeros(out.shape, out.dtype)
        copy.wait()

    @pl.when(i < meta_ref[0])
    def _():
        e = ids_ref[i]
        start, count = start_ref[e], count_ref[e]

        def block(b, carry):
            row0 = start + b * rb
            rows = jnp.minimum(rb, count - b * rb)

            def take(r, carry):
                x_blk[pl.ds(r, 1), :] = x_all[pl.ds(token_ref[row0 + r], 1), :]
                return carry

            jax.lax.fori_loop(0, rows, take, 0)
            y_blk[...] = expert_mlp(x_blk[...].astype(dtype), *(r[0] for r in weight_refs))

            def give(r, carry):
                to = pl.ds(token_ref[row0 + r], 1)
                out[to, :] += weight_ref[row0 + r] * y_blk[pl.ds(r, 1), :]
                return carry

            jax.lax.fori_loop(0, rows, give, 0)
            return carry

        jax.lax.fori_loop(0, (count + (rb - 1)) // rb, block, 0)

    @pl.when((i == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _():
        copy = pltpu.make_async_copy(out, o_hbm, sem.at[0])
        copy.start()
        copy.wait()


def _grouped_pallas(x, ids, count, starts, counts, token, weight, weights, first, *,
                    interpret: bool, bf: int | None = None):
    """The grouped form as ONE kernel over the compacted list: the weight
    blocks are the batch kernel's (expert ``i + 1``'s in flight while expert
    ``i`` multiplies, each read once however many pairs the expert has); the
    ordering is scalar prefetch."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (n, d), experts, f, m = x.shape, ids.shape[0], weights[0].shape[-1], len(weights)
    size = weights[0].dtype.itemsize
    bf = bf or block_f(d, f, size, matrices=m)
    rows, rb = n + -n % 8, row_block(n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(experts, f // bf),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY), *_weight_specs(d, bf, f // bf, m)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rb, d), jnp.float32),
            pltpu.VMEM((rb, d), jnp.float32),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, dtype=x.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the matrices' blocks, double-buffered, the batch's rows and
            # their sums, a block of rows and its results, and the rest
            vmem_limit_bytes=2 * m * d * bf * size + 2 * (rows + rb) * d * 4 + (16 << 20),
        ),
        interpret=interpret,
        name="moe_grouped_experts",
    )(ids, jnp.stack([count, jnp.asarray(first, jnp.int32)]), starts, counts, token, weight,
      jnp.pad(x.astype(jnp.float32), ((0, rows - n), (0, 0))), *weights)
    return out[:n]


def expert_layer(x, mask, wmat, *weights, first=0, top_k: int | None = None,
                 tile: int = TILE, impl: str = "auto"):
    """``sum_e wmat[:, e] * Expert_e(x)`` over exactly the pairs in ``mask``.
    x: (N, d) in the products' dtype; mask, wmat: (N, E); ``weights``: the
    experts' matrices, ``(gate, up, down)`` of a gated expert or ``(up,
    down)`` of an ungated one (``expert_mlp``: the weights choose), gate, up:
    (.., d, f), down: (.., f, d): expert e's weights at ``first + e`` (``first`` may
    be traced: the experts of EVERY layer in one array, so that a layer
    loop hands this one no slice of them -- XLA would copy it, all of a
    layer's experts a layer).  ``top_k``: the most pairs a row of ``mask``
    has (the router's; a shape fact, the grouped form's bound on pairs; none
    given, every held expert).  Returns (N, d) float32; a row's experts are
    added in order, so the sum does not depend on the load's shape.  ``N <=
    tile`` takes the batch form, ``N > tile`` the grouped form (``impl``:
    ``pallas`` the form's kernel, ``xla`` plain ``jax.numpy``, ``auto`` the
    kernel on a TPU)."""
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown expert impl {impl!r}; expected 'auto', 'xla' or 'pallas'")
    n = mask.shape[0]
    plain = impl == "xla" or (impl == "auto" and not _on_tpu())
    ids, count = touched(mask)
    if n <= tile:
        args = (x, jnp.where(mask, wmat, 0.0).T, ids, count, weights, first)
        return _batch_xla(*args) if plain else _batch_pallas(*args, interpret=not _on_tpu())
    args = (x, ids, count, *expert_order(mask, wmat, top_k), weights, first)
    return _grouped_xla(*args) if plain else _grouped_pallas(*args, interpret=not _on_tpu())
