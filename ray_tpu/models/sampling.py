"""Per-token sampling shared by the decode paths and the LLM engine.

Two helpers:

* ``sample_tokens`` — greedy / temperature / top-k / top-p over a batch
  of next-token logit rows, with every knob accepted either as a scalar
  (whole batch) or as a per-row array (the continuous-batching engine
  mixes requests with different sampling params in one decode step).
* ``speculative_verify`` — the accept/reject half of speculative
  decoding for ONE sequence's ``w = k+1``-position verification window,
  by SAMPLE-THEN-MATCH: position ``i`` draws the target token ``t_i``
  from the SAME filtered distribution (and the same per-index PRNG key)
  ``sample_tokens`` would have used at that output index, then accepts
  the drafted prefix while ``draft_i == t_i`` and always emits ``t_i``.
  Both built-in drafters are deterministic (point-mass proposals), so
  this has exactly the acceptance probability of textbook rejection
  sampling — accept ``x_i`` with ``p_i(x_i)``, i.e. ``min(1, p/q)`` with
  ``q`` a point mass — while being stronger than the delta/residual
  formulation where it matters: the emitted token at output index ``i``
  depends only on ``(seed, i, prefix)``, never on where the verification
  window happened to start, so sampled speculative decode is per-seed
  reproducible across runs AND token-identical to the non-speculative
  sampled path (greedy falls out as the ``temperature <= 0`` argmax
  special case).

Everything is jit-safe with static shapes, and the sampler never leaves
SORTED order (PR 27).  Per-row ``k`` and ``p`` need a rank and a cumulative
mass, so a row is sorted once, descending and stable, by its
temperature-scaled logits; the sort carries, beside that key, the
vocabulary index and the Gumbel noise ``jax.random.categorical`` would
have added to each token (``lax.sort`` with three operands, one key), so
values, token ids and noise arrive aligned with no gather.  The top-k /
top-p mask, the draw (the maximum of masked value + noise, the lowest
token id among exact ties, as an argmax in vocabulary order breaks them)
and the chosen token's logprob are all taken where the sort left the row:
nothing is scattered back, since no caller needs filtered logits in
vocabulary order.  The tokens did not change: a row's key, the noise
drawn from it, the stable order and the mask are what they were, and
``max(x[order] + g[order])`` picks the token ``max(x + g)`` picks.

Only the rows that SAMPLE are sorted (PR 46).  The sort, the noise made for
it, the softmax and the running sum behind top-p are all per row, and an
engine's batch is mostly greedy rows and empty slots.  So the sampled rows
are packed to the front, in slot order, and drawn at the narrowest of a few
FIXED widths that holds them: ``sort_ladder(b)`` is 8, doubling, ending at
the batch itself (8, 16, 32, 64 for 64 slots; 1 for the prefill program's
row), and one ``lax.switch`` inside the one compiled program picks the rung
from the count of ``temperature > 0``, read on the device (``sort_rung``,
which the engine's ``stats()["sampler"]`` counter calls too).  A batch with
no sampled row (empty engine slots included) takes branch 0: no sort, the
two greedy reductions.  A row's draw depends on its logits, its key and its
knobs and on nothing of its neighbours, so which rows stand beside it, how
many, and at which width it was sorted change nothing it sees: tokens and
logprobs are those of sorting every row, bit for bit on a CPU
(tests/test_sampling_sorted.py keeps that program as a reference).  On the
chip the tokens are equal too, and a logprob may differ in its last place:
there the order of a row's ``sum(exp(.))`` follows the compiled operand's
row count, as it always did between the prefill program's one row, the
decode's slots and a verify window's ``S x W`` (PERF.md section 6, PR 46).
``token_logprobs`` alone keeps the filter in vocabulary order
(``_filtered_logits``): it scores GIVEN ids and is differentiated.

Convention: ``temperature <= 0`` means greedy (argmax) for that row —
the PRNG key is still consumed uniformly so a batch mixing greedy and
sampled rows stays deterministic per-row regardless of its neighbors.

The (seed, absolute output index) keying is ALSO the serve plane's
mid-stream-failover guarantee (RESILIENCE.md): a replica that dies
mid-stream is replaced by re-submitting prompt + delivered tokens
(``LLMEngine.submit(resume_tokens=...)``), and because the token at
output index ``i`` depends only on ``(seed, i, prefix)`` — never on
which replica, verification window, or failover attempt produced it —
the resumed stream is token-identical to the unkilled run, under greedy
and seeded sampling alike. Any future sampling change MUST preserve
this: key by absolute output position, not by step/window/attempt.

Logprob capture (the ``ray_tpu.rlhf`` behavior-policy contract): every
sampling entry point has a ``*_logprobs`` variant that also returns the
log-probability of the CHOSEN token under the exact distribution it was
drawn from — ``log_softmax`` of the temperature-scaled, top-k/top-p
masked logits for sampled rows, ``log_softmax`` of the raw logits at the
argmax for greedy rows (a point mass has no useful density; the raw
model confidence is the informative number and is what a scorer
recomputing ``log_softmax`` at the greedy id gets). ``token_logprobs``
is the matching SCORING entry point: given token ids instead of a PRNG
key it returns the same quantity, so an RLHF learner can evaluate its
current policy on rollout tokens in exactly the units the engine
captured behavior logprobs in — the importance ratio
``exp(current - behavior)`` is then exact, whatever sampling knobs the
rollout used. Since both are pure functions of (logits, knobs, id), the
captured value at output index ``i`` inherits the failover contract
above: identical across spec-decode window alignments, resumes, and
replicas.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


#: the widest running sum XLA runs as one ``reduce_window``: a wider one it
#: rewrites itself, into these same levels, under ops that carry no name
_SCAN_WIDTH = 128


def _running_sum(x):
    """``jnp.cumsum(x, axis=1)`` of (b, n) float rows, in the levels XLA
    itself breaks a long running sum into: tiles of 128 summed along the
    tile (a ``reduce_window`` it runs as it stands), each tile then
    raised by the sum of the tiles before it (the same, one level up).
    Written out here because the ops of XLA's own rewrite carry no
    ``op_name`` (and jax lowers ``cumsum`` out of line, which drops the
    name stack): a trace would count them to no scope, not to the
    sampler."""
    b, n = x.shape
    if n <= _SCAN_WIDTH:
        return jax.lax.reduce_window(
            x, 0.0, jax.lax.add, (1, n), (1, 1), ((0, 0), (n - 1, 0))
        )
    tiles = -(-n // _SCAN_WIDTH)
    x = jnp.pad(x, ((0, 0), (0, tiles * _SCAN_WIDTH - n)))
    within = jax.lax.reduce_window(
        x.reshape(b, tiles, _SCAN_WIDTH), 0.0, jax.lax.add,
        (1, 1, _SCAN_WIDTH), (1, 1, 1), ((0, 0), (0, 0), (_SCAN_WIDTH - 1, 0)),
    )
    # what stands before a tile: the running sum of the tiles' totals, shifted
    totals = jax.lax.slice(
        within, (0, 0, _SCAN_WIDTH - 1), (b, tiles - 1, _SCAN_WIDTH)
    ).reshape(b, tiles - 1)
    before = _running_sum(jnp.pad(totals, ((0, 0), (1, 0))))
    return (within + before[:, :, None]).reshape(b, tiles * _SCAN_WIDTH)[:, :n]


def _keep_sorted(sorted_scaled, kk, pp):
    """THE top-k / top-p filter, as a mask over the RANKS of rows sorted
    descending: rank < k for top-k (``k <= 0``: off), exclusive cumulative
    mass < p for top-p (rank 0 always survives).  sorted_scaled: (b, v)
    fp32; kk/pp: (b,).  One sort serves both truncations."""
    ranks = jax.lax.broadcasted_iota(jnp.int32, sorted_scaled.shape, 1)
    probs = jax.nn.softmax(sorted_scaled, axis=-1)
    cum = _running_sum(probs)
    keep = (kk[:, None] <= 0) | (ranks < kk[:, None])
    return keep & ((cum - probs) < pp[:, None])


def _filtered_logits(logits, temp, kk, pp):
    """Temperature-scaled logits with top-k/top-p support masked to
    ``_NEG_INF``, in VOCABULARY order: the distribution the sampler draws
    from, for ``token_logprobs`` alone (it scores given ids and is
    differentiated; the sampler itself stays in sorted order and pays
    neither this gather nor this scatter).  logits: (b, v) fp32;
    temp/kk/pp: (b,) arrays."""
    scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
    order = jnp.argsort(-scaled, axis=-1)
    sorted_scaled = jnp.take_along_axis(scaled, order, axis=-1)
    masked_sorted = jnp.where(
        _keep_sorted(sorted_scaled, kk, pp), sorted_scaled, _NEG_INF
    )
    # scatter the surviving logits back to vocab order
    return (
        jnp.full_like(scaled, _NEG_INF)
        .at[jnp.arange(logits.shape[0])[:, None], order]
        .set(masked_sorted)
    )


#: the narrowest batch the sampler sorts: one float32 vreg holds 8 rows
_FIRST_RUNG = 8


def sort_ladder(b):
    """The widths the sampler may sort at, for a batch of ``b`` rows: 0 (no
    row samples, no sort), then a first rung, doubling, ending at ``b``
    itself — (0, 8, 16, 32) for 32 rows, (0, 1) for the prefill program's
    one.  Fixed by the operand's shape alone."""
    widths = [0]
    w = _FIRST_RUNG
    while w < b:
        widths.append(w)
        w *= 2
    return (*widths, b)


def sort_rung(n, b):
    """Index into ``sort_ladder(b)`` of the narrowest width that holds ``n``
    sampled rows.  ``n`` is a traced count on the device (``_draw_rows``'s
    ``switch``) or an int on the host (the engine's ``stats()["sampler"]``):
    one rule for both."""
    return sum((n > w) * 1 for w in sort_ladder(b)[:-1])


def _draw_rows(logits, keys, temp, kk, pp):
    """The sampler: one token and its logprob a row.  logits: (b, v) fp32;
    keys: (b, 2) — row i's is the key ``jax.random.categorical`` would be
    handed for it; temp/kk/pp: (b,).  Returns (tokens (b,) int32, logprobs
    (b,) fp32) under the module-doc conventions.

    Greedy rows are two reductions over the raw logits.  Sampled rows are
    drawn where ONE stable descending sort leaves them (module doc): it
    carries each token's id and Gumbel noise beside the key, so neither a
    vocabulary-wide gather nor a scatter follows it.  Only the rows that
    sample are sorted: a ``switch`` on their count, read on the device,
    packs them into the narrowest width of ``sort_ladder(b)`` that holds
    them, so one compiled program serves an all-greedy batch at the cost of
    its reductions and one sampled row among 64 slots at the cost of 8."""
    b, v = logits.shape
    sampled_row = temp > 0.0
    top = jnp.max(logits, axis=-1)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # log_softmax(logits) at the argmax, where the shifted logit is 0
    greedy_lp = -jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), axis=-1))

    def sorted_draw(w):
        # the w rows to sort: the sampled ones in slot order, then padding
        # (index b: a copy of the last row, its result dropped).  A row's
        # place is the count of sampled rows before it, by comparison: no
        # sort of slots, and no ``cumsum``, which jax lowers out of line,
        # so that a trace would count it to no scope (``_running_sum``)
        slot = jnp.arange(b, dtype=jnp.int32)
        place = jnp.sum(sampled_row[None, :] & (slot[None, :] < slot[:, None]), axis=1)
        pick = sampled_row[None, :] & (place[None, :] == slot[:w, None])
        rows = jnp.min(jnp.where(pick, slot[None, :], b), axis=1)
        lg, ks, t, k, p = (
            jnp.take(x, rows, axis=0, mode="clip") for x in (logits, keys, temp, kk, pp)
        )
        scaled = lg / jnp.maximum(t, 1e-6)[:, None]
        # the noise categorical(key, row) adds to a (v,) row, token by token
        noise = jax.vmap(lambda key: jax.random.gumbel(key, (v,), jnp.float32))(ks)
        ids = jax.lax.broadcasted_iota(jnp.int32, (w, v), 1)
        neg, order, noise = jax.lax.sort(
            (-scaled, ids, noise), dimension=1, is_stable=True, num_keys=1
        )
        masked = jnp.where(_keep_sorted(-neg, k, p), -neg, _NEG_INF)
        # Gumbel-max; among exact ties the lowest token id, as an argmax in
        # vocabulary order would break them
        z = masked + noise
        tok = jnp.min(
            jnp.where(z == jnp.max(z, axis=-1, keepdims=True), order, v), axis=-1
        )
        chosen = jnp.max(
            jnp.where(order == tok[:, None], masked, -jnp.inf), axis=-1
        )
        # log_softmax(masked) at the token: rank 0 survives every filter,
        # so masked[:, 0] is the row's maximum
        norm = jnp.log(jnp.sum(jnp.exp(masked - masked[:, :1]), axis=-1))
        lp = (chosen - masked[:, 0]) - norm
        return (
            greedy.at[rows].set(tok, mode="drop"),
            greedy_lp.at[rows].set(lp, mode="drop"),
        )

    return jax.lax.switch(
        sort_rung(jnp.sum(sampled_row), b),
        [lambda: (greedy, greedy_lp)]
        + [functools.partial(sorted_draw, w) for w in sort_ladder(b)[1:]],
    )


def _request_keys(seeds, counters):
    """The engine's row keys — the (seed, absolute output index) keying of
    the module doc.  Output index ``counters[i]`` of the request seeded
    ``seeds[i]`` draws with ``split(fold_in(PRNGKey(seed), index), 1)[0]``
    (the ``split`` is ``sample_tokens_logprobs``'s per-row split at a batch
    of one, which is how this key was first derived: kept, so that every
    (seed, index) draws the token it always drew)."""
    return jax.vmap(
        lambda s, c: jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(s), c), 1
        )[0]
    )(seeds, counters)


def _broadcast_knobs(b, temperature, top_k, top_p):
    temp = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (b,))
    kk = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (b,))
    pp = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (b,))
    return temp, kk, pp


def _chosen_logprob(logits, masked, temp, tok):
    """Module-doc logprob convention: sampled rows score under the
    filtered distribution they drew from, greedy rows under the raw
    logits (log_softmax at the argmax id)."""
    idx = tok[:, None]
    lp_sampled = jnp.take_along_axis(
        jax.nn.log_softmax(masked, axis=-1), idx, axis=-1
    )[:, 0]
    lp_greedy = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), idx, axis=-1
    )[:, 0]
    return jnp.where(temp > 0.0, lp_sampled, lp_greedy)


def sample_tokens_logprobs(
    logits: jax.Array,
    key: jax.Array,
    temperature=0.0,
    top_k=0,
    top_p=1.0,
) -> tuple[jax.Array, jax.Array]:
    """``sample_tokens`` that also returns each chosen token's logprob
    ((batch,) float32) under the module-doc convention — the behavior
    logprob the RLHF importance ratio needs, captured at zero extra
    model cost (the softmax already exists on device)."""
    logits = logits.astype(jnp.float32)
    b = logits.shape[0]
    temp, kk, pp = _broadcast_knobs(b, temperature, top_k, top_p)
    return _draw_rows(logits, jax.random.split(key, b), temp, kk, pp)


def sample_tokens(
    logits: jax.Array,
    key: jax.Array,
    temperature=0.0,
    top_k=0,
    top_p=1.0,
) -> jax.Array:
    """Sample one token id per row: (batch, vocab) fp logits -> (batch,) int32.

    ``temperature``/``top_k``/``top_p`` are scalars or (batch,) arrays.
    ``top_k <= 0`` disables the k-truncation; ``top_p >= 1`` the nucleus
    truncation; ``temperature <= 0`` selects greedy argmax for that row.
    ``key`` is one PRNG key for the whole call — rows draw from
    per-row splits so the same (key, row) pair always reproduces.
    """
    return sample_tokens_logprobs(logits, key, temperature, top_k, top_p)[0]


def sample_rows_logprobs(logits, seeds, counters, temperature, top_k, top_p):
    """The engine's decode sampler: row i is output index ``counters[i]``
    of the request seeded ``seeds[i]`` and draws from those two alone, so
    a request gets the same tokens whatever slot, step or replica it lands
    in.  logits: (n, vocab); every other operand (n,).  ONE batched call,
    so the ``switch`` of ``_draw_rows`` is a real branch, not a select.  Returns
    (tokens (n,), logprobs (n,))."""
    logits = logits.astype(jnp.float32)
    temp, kk, pp = _broadcast_knobs(logits.shape[0], temperature, top_k, top_p)
    return _draw_rows(logits, _request_keys(seeds, counters), temp, kk, pp)


def token_logprobs(
    logits: jax.Array,
    tokens: jax.Array,
    temperature=0.0,
    top_k=0,
    top_p=1.0,
) -> jax.Array:
    """Score GIVEN token ids under the exact sampling distribution:
    (batch, vocab) logits + (batch,) int32 ids -> (batch,) float32
    logprobs, same convention as ``sample_tokens_logprobs`` (module doc).

    This is the learner-side half of the RLHF importance ratio: the
    engine captures behavior logprobs with ``sample_tokens_logprobs``;
    the learner recomputes current-policy logprobs of the same tokens
    with THIS function and the same knobs, so ``exp(cur - behavior)`` is
    an exact density ratio. Differentiable w.r.t. ``logits`` (the
    top-k/top-p mask is treated as constant, standard straight-through
    practice for truncated-sampling objectives).

    A token the filter masked out scores ``-inf``-like (≈ -1e30 shifted
    by the log-normalizer): it had probability 0 under the behavior
    distribution, which is exactly what the ratio math wants.
    """
    logits = logits.astype(jnp.float32)
    b, v = logits.shape
    temp, kk, pp = _broadcast_knobs(b, temperature, top_k, top_p)
    masked = _filtered_logits(logits, temp, kk, pp)
    return _chosen_logprob(logits, masked, temp, tokens.astype(jnp.int32))


def verify_rows_logprobs(logits, draft, seeds, counters, temperature, top_k, top_p):
    """Sample-then-match for a batch of verification windows (module doc;
    ``speculative_verify`` has the contract).  logits: (S, W, vocab);
    draft: (S, W-1); seeds/counters: (S,) — the request's seed and the
    output index of its window's first token; knobs: (S,), one value a
    window.  Window index i of slot s draws with ``sample_rows_logprobs``'s key for
    (seeds[s], counters[s] + i), all S·W rows in one batched call.
    Returns (n_accepted (S,), out (S, W), logprobs (S, W))."""
    s, w, v = logits.shape
    index = counters[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    out, logp = sample_rows_logprobs(
        logits.reshape(s * w, v),
        jnp.repeat(seeds, w),
        index.reshape(-1),
        *(jnp.repeat(knob, w) for knob in (temperature, top_k, top_p)),
    )
    out, logp = out.reshape(s, w), logp.reshape(s, w)
    accept = (draft == out[:, : w - 1]).astype(jnp.int32)
    # an empty draft (w == 1) accepts nothing: the window is the bonus position
    n_acc = jnp.sum(jnp.cumprod(accept, axis=1), axis=1).astype(jnp.int32)
    return n_acc, out, logp


def speculative_verify_logprobs(
    logits: jax.Array,
    draft: jax.Array,
    seed: jax.Array,
    counter: jax.Array,
    temperature=0.0,
    top_k=0,
    top_p=1.0,
):
    """``speculative_verify`` that also returns (w,) logprobs of the
    emitted tokens — window index ``i``'s entry scores ``out[i]`` under
    the exact per-index filtered distribution (same convention as
    ``sample_tokens_logprobs``), so spec-decode rollouts capture behavior
    logprobs identical to the plain decode path's (verification already
    computes every per-index distribution; reading the chosen density is
    free). Validity mirrors ``out``: entries past ``n_accepted`` are
    conditioned on a rejected prefix and must be discarded with their
    tokens."""
    n_acc, out, logp = verify_rows_logprobs(
        logits[None],
        draft[None],
        jnp.asarray(seed)[None],
        jnp.asarray(counter, jnp.int32)[None],
        *_broadcast_knobs(1, temperature, top_k, top_p),
    )
    return n_acc[0], out[0], logp[0]


def speculative_verify(
    logits: jax.Array,
    draft: jax.Array,
    seed: jax.Array,
    counter: jax.Array,
    temperature=0.0,
    top_k=0,
    top_p=1.0,
):
    """Accept/reject one sequence's drafted window against target logits.

    ``logits``: (w, vocab) — the target model's logits at the window's w
    positions (position i conditioned on the draft tokens before it);
    ``draft``: (w-1,) int32 — the drafter's proposals; ``seed``/``counter``
    — the request's sampling seed and the output index of the window's
    FIRST token.

    Sample-then-match (module doc): window index i draws ``out[i]`` with
    the PRNG key ``fold_in(PRNGKey(seed), counter + i)`` — the exact key
    AND filtered distribution the plain decode path's per-row sampler
    uses at that output index — then the drafted prefix is accepted while
    ``draft[i] == out[i]``.  ``out[i]`` is only CONDITIONALLY valid: its
    logits assumed the draft prefix before it, which holds exactly up
    through the first mismatch, so callers emit ``out[:n_accepted + 1]``
    (accepted prefix + one correction/bonus token — the first mismatch's
    replacement, or the bonus position when everything matched) and
    ignore the rest.

    Greedy (``temperature <= 0``) accepts while ``draft[i] == argmax`` —
    the emitted chain is exactly the sequential argmax chain.  Either
    way the emitted token at output index i depends only on
    (seed, i, prefix): identical to non-speculative decode, whatever the
    drafter proposed and wherever the window boundaries fell.
    """
    n_acc, out, _ = speculative_verify_logprobs(
        logits, draft, seed, counter, temperature, top_k, top_p
    )
    return n_acc, out
