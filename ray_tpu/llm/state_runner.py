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

``HybridModelRunner`` is the same for a model whose sequences hold blocks
of K/V AND a slot of state (``cache_kind == "hybrid"``,
``cache.HybridPool``; ``models.phi4flash`` is the one such family): the
body gives the whole layer program of each step, ``decode(params, x,
arrays, positions, tables) -> (hidden, arrays)`` and ``chunk(params, x,
arrays, start, n_valid, table) -> (last hidden, arrays)``, because its
layers are of several kinds in several loops; ``arrays`` is the pool's
tuple, every one donated and handed back in its place, and a table row is
``[slot, block table...]``.  A dead decode row feeds position 0 of the
trash slot and the trash block.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.model_runner import (
    StepRunner,
    _advance_slots,
    _carry_loop,
    _decode_sample,
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
        # the K/V pool's two arrays and one a kind of state
        self.n_arrays = n = 2 + len(self.body.state_leaves(block_size))
        pools = tuple(range(1, 1 + n))
        self._decode = jax.jit(self._decode_impl, donate_argnums=pools + (1 + n,))
        self._prefill = jax.jit(
            self._prefill_impl, donate_argnums=pools, static_argnames=("chunk",)
        )

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

    def decode_step(self, *operands):
        return self._call(
            "decode", self._decode, jnp.shape(operands[-2])[0], self.params, *operands)

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
        return self._call(
            "prefill", self._prefill, len(tokens),
            self.params, *arrays, tokens,
            np.int32(start), np.int32(n_valid), table, sampling, chunk=len(tokens),
        )
