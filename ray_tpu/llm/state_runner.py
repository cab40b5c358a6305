"""Jitted decode and prefill-chunk steps of a model whose sequences hold a
fixed-size recurrent STATE and no keys or values (``cache.StatePool``).

The runner names no family.  A model configuration with ``cache_kind ==
"state"`` gives its traced layer math through ``serving_body()``:
``embed(params, tokens)``, ``decode_layer(x, layer, state, base, positions,
slots, live)``, ``chunk_layer(x, layer, state, base, positions, slot,
valid)``, ``lm_head(params, h)``, and ``state_shape`` / ``state_dtype`` of
one sequence in one layer (``models.brumby`` is the one such family).

Everything around the layers is ``model_runner``'s: the slot state that
lives on the device (``_merge_slots``, ``_advance_slots``, ``PATCH_*``),
the sampler, and the layer loop — the state pool rides ``_carry_loop`` as
its one donated carry, so a step updates the buffer it was given
(``tests/test_llm_brumby_parity.py`` holds both steps to it through the
compiled program's temporary size).  The "block table" of a sequence is
one entry wide: the slot of the pool it owns.  A decode row that is not
live touches no state; a prefill chunk at position 0 overwrites what the
slot's last owner left.

The step signatures are the paged runner's with the pool's one array where
that has two: ``decode_step(state, carry, first_tok, patch, tables, knobs)
-> (state, carry, tokens, logprobs)`` and ``prefill_chunk(state, tokens,
start, n_valid, table, sampling) -> (state, logits, token, logprob)``.
There is no verify step and no block fork: ``LLMEngine`` refuses
speculation, the prefix cache and ``tp > 1`` for such a model.

``HybridModelRunner`` is the same for a model whose body gives the whole
layer program of each step, ``decode(params, x, arrays, positions, tables)
-> (hidden, arrays)`` and ``chunk(params, x, arrays, start, n_valid, table)
-> (last hidden, arrays)``, because its layers are of several kinds in
several loops; ``arrays`` is the pool's tuple, every one donated and handed
back in its place.  Two kinds of family take it:

* sequences hold blocks of K/V AND a slot of state (``cache_kind ==
  "hybrid"``, ``cache.HybridPool``; ``models.phi4flash``: ONE shared K/V
  layer; ``models.falcon_h1``: K/V in every layer; ``models.granite_h``: K/V
  in the attention layers of a pattern, state in the others): a table row is ``[slot,
  block table...]``, and a dead decode row feeds position 0 of the trash slot
  and the trash block;
* sequences hold blocks alone, of whatever the body's ``kv_layout()`` says a
  token leaves behind (``cache_kind == "paged"``, a ``cache.KVBlockPool``;
  ``models.kimi_k2``: one array of latent rows): a table row is the block
  table, blocks are shared, so the runner has ``fork_blocks`` and the engine
  runs the radix prefix cache over it;
* sequences hold blocks of TWO layer kinds, the window layers' handed back
  behind the window (``cache_kind == "windowed"``, a ``cache.LayerTypedPool``;
  ``models.afmoe``): four paged arrays, a table row is the full layers' table
  followed by the window layers', and nothing is shared.

A body may count on the device (``counters()``: shapes; ``models.kimi_k2``
and ``models.granite_h`` count the router's load): those arrays ride every step after the pool's
and are handed back, NOT donated (a few dozen numbers), and stay HERE and not
in the pool; ``counters()`` of the runner gives a reader that fetches them
(``LLMEngine.stats()`` alone does, outside its lock).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.cache import KVBlockPool, LayerTypedPool
from ray_tpu.llm.model_runner import (
    StepRunner,
    _advance_slots,
    _carry_loop,
    _decode_sample,
    _fork_impl,
    _merge_slots,
    _prefill_sample,
)


class StateModelRunner(StepRunner):
    arch = "state"

    def __init__(self, cfg: Any, params: dict):
        super().__init__(cfg, params)
        self.body = cfg.serving_body()
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1, 2))
        self._prefill = jax.jit(
            self._prefill_impl, donate_argnums=(1,), static_argnames=("chunk",)
        )

    def _decode_logits(self, params, state, tokens, positions, slots, alive):
        """The model's part of a decode: row i feeds ``tokens[i]`` at
        ``positions[i]`` to the state in slot ``slots[i]`` where
        ``alive[i]``.  Returns (state, logits (S, V))."""
        body = self.body
        x = body.embed(params, tokens)

        def layer_fn(x, layer, state, base):
            return body.decode_layer(x, layer, state, base, positions, slots, alive)

        x, state = _carry_loop(params["blocks"], x, (state,), layer_fn)
        return state, body.lm_head(params, x)

    def _decode_impl(self, params, state, carry, first_tok, patch, tables, knobs):
        """state: (L, slots) + state_shape; the rest as
        ``PagedModelRunner._decode_impl``, ``tables`` (S, 1)."""
        tokens, positions, counters = _merge_slots(carry, first_tok, patch)
        state, logits = self._decode_logits(
            params, state, tokens, positions, tables[:, 0], knobs[:, 0] > 0)
        live, nxt, logp = _decode_sample(logits, knobs, counters)
        return state, _advance_slots(live, nxt, positions, counters), nxt, logp

    def decode_step(self, state, carry, first_tok, patch, tables, knobs):
        return self._call(
            "decode", self._decode, jnp.shape(tables)[0],
            self.params, state, carry, first_tok, patch, tables, knobs,
        )

    def _prefill_impl(self, params, state, tokens, start, n_valid, table, sampling,
                      *, chunk: int):
        body = self.body
        positions = start + jnp.arange(chunk, dtype=jnp.int32)
        valid = jnp.arange(chunk) < n_valid
        x = body.embed(params, tokens)

        def layer_fn(x, layer, state, base):
            return body.chunk_layer(x, layer, state, base, positions, table[0], valid)

        x, state = _carry_loop(params["blocks"], x, (state,), layer_fn)
        last = x[jnp.maximum(n_valid - 1, 0)]
        logits = body.lm_head(params, last[None, :])[0]  # (V,)
        tok, logp = _prefill_sample(logits, sampling)
        return state, logits, tok, logp

    def prefill_chunk(self, state, tokens, start, n_valid, table, sampling):
        return self._call(
            "prefill", self._prefill, len(tokens),
            self.params, state, tokens,
            np.int32(start), np.int32(n_valid), table, sampling, chunk=len(tokens),
        )


class HybridModelRunner(StepRunner):
    arch = "hybrid"

    def __init__(self, cfg: Any, params: dict, block_size: int):
        super().__init__(cfg, params)
        self.body = cfg.serving_body()
        #: what the body counts on the device; none for most
        self._counts = tuple(
            jnp.zeros(c.shape, c.dtype) for c in getattr(self.body, "counters", tuple)())
        # the paged pool's arrays (K and V, or what the body's layout says:
        # one array, or K and V of each of two layer kinds), one a kind of
        # state, and the body's counters
        layout = self.body.kv_layout()
        self.n_paged = (LayerTypedPool if "kinds" in layout else KVBlockPool).n_arrays(**layout)
        self.n_arrays = n = (
            self.n_paged + len(self.body.state_leaves(block_size)) + len(self._counts))
        # the pool's arrays are donated; the counters are not, so that a
        # reader holding them (``counters()``) outlives the next step
        pools = tuple(range(1, 1 + n - len(self._counts)))
        self._decode = jax.jit(self._decode_impl, donate_argnums=pools + (1 + n,))
        self._prefill = jax.jit(
            self._prefill_impl, donate_argnums=pools, static_argnames=("chunk",)
        )
        self._fork = jax.jit(_fork_impl, donate_argnums=tuple(range(self.n_paged)))

    def _decode_logits(self, params, arrays, tokens, positions, tables):
        """The model's part of a decode.  Returns (arrays, logits (S, V))."""
        body = self.body
        x, arrays = body.decode(params, body.embed(params, tokens), arrays, positions, tables)
        return arrays, body.lm_head(params, x)

    def _decode_impl(self, params, *rest):
        """rest: the pool's arrays, then ``carry, first_tok, patch, tables,
        knobs`` as ``PagedModelRunner._decode_impl``, ``tables`` (S, 1 + T)."""
        arrays, (carry, first_tok, patch, tables, knobs) = (
            rest[:self.n_arrays], rest[self.n_arrays:])
        tokens, positions, counters = _merge_slots(carry, first_tok, patch)
        arrays, logits = self._decode_logits(params, arrays, tokens, positions, tables)
        live, nxt, logp = _decode_sample(logits, knobs, counters)
        return (*arrays, _advance_slots(live, nxt, positions, counters), nxt, logp)

    def _with_counts(self, site, fn, key, operands, n_pool: int, **static):
        """Run a step with the body's counters after the pool's arrays, and
        keep what it hands back of them."""
        k = len(self._counts)
        out = self._call(
            site, fn, key, self.params, *operands[:n_pool], *self._counts,
            *operands[n_pool:], **static)
        self._counts = tuple(out[n_pool:n_pool + k])
        return (*out[:n_pool], *out[n_pool + k:])

    def counters(self):
        """A reader of what the body has counted on the device in the steps
        launched so far: called, it fetches (and waits for those steps) and
        gives sections of ``stats()`` under the body's own names, {} for a
        body that counts nothing.  Take it under the engine's lock and call
        it outside: the arrays it holds are never donated."""
        counts, body = self._counts, self.body
        return lambda: body.read_counters(counts) if counts else {}

    def decode_step(self, *operands):
        return self._with_counts(
            "decode", self._decode, jnp.shape(operands[-2])[0], operands, len(operands) - 5)

    def _prefill_impl(self, params, *rest, chunk: int):
        arrays, (tokens, start, n_valid, table, sampling) = (
            rest[:self.n_arrays], rest[self.n_arrays:])
        body = self.body
        last, arrays = body.chunk(
            params, body.embed(params, tokens), arrays, start, n_valid, table)
        logits = body.lm_head(params, last)[0]  # (V,)
        tok, logp = _prefill_sample(logits, sampling)
        return (*arrays, logits, tok, logp)

    def prefill_chunk(self, *operands):
        *arrays, tokens, start, n_valid, table, sampling = operands
        return self._with_counts(
            "prefill", self._prefill, len(tokens),
            (*arrays, tokens, np.int32(start), np.int32(n_valid), table, sampling),
            len(arrays), chunk=len(tokens),
        )

    def fork_blocks(self, *operands):
        """Copy-on-write for the prefix cache: blocks ``src`` onto ``dst`` in
        every layer of every PAGED array (``model_runner._fork_impl``, for
        whatever a block holds).  The engine refuses the prefix cache for a
        body with state, so the state's leaves pass through untouched."""
        *arrays, src, dst = operands
        forked = self._call(
            "fork", self._fork, len(src), *arrays[:self.n_paged],
            jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32))
        return (*forked, *arrays[self.n_paged:])
