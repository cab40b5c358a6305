"""Family ``afmoe``: arcee-ai/Trinity-Large-Preview's ``config.json`` keys
(``model_type`` ``afmoe``) onto ``ray_tpu.models.afmoe``; plain reference
``benchmark/reference/afmoe.py``.

The family's pieces, all found by name (nothing the benchmark had is edited):

* ``model_config`` reads the published keys and refuses a file whose other
  published keys say something the program does not do (another activation or
  scoring rule, an unnormalised top-k, a limit on expert groups, a tied head, a
  rope scaling, more than one shared expert, a pattern that is not three
  sliding layers to a full one).  The published ``layer_types`` is kept whole;
  the layers run here are ``num_hidden_layers`` of them from
  ``deployment.first_layer`` on, the first ``num_dense_layers`` of THOSE dense.
  The file's ``num_experts`` and ``vocab_size`` are what THIS CHIP holds (both
  under ``reduced``); the router's published width and the chip's place among
  those that share a layer stand in ``deployment`` (``router_experts``,
  ``expert_parallel``, ``expert_offset``).  What the published config does NOT
  give stands in the file's ``route_eps``, ``init_range``, ``attention_init``
  and ``expert_init`` and is explained under its ``assumed``.
* ``reference_logits``: the equations over the whole sequence in float32
  (``reference/afmoe.py``): a dense masked softmax with the window as a MASK, a
  loop over the held experts, no cache.  The program serves chunks over two
  block tables, hands window blocks back and decodes through what is left, so
  the comparison that decides ``correct`` holds one to the other.  A row whose
  routing lies within ``ROUTING_MARGIN`` of a flip comes back all zero (the
  ``reference_rows`` line says how many).
* the counts the roofline readers use, all of what the MATHEMATICS moves,
  unpadded, so a share of them cannot pass 100%:
  ``gqa_decode_kv_bytes(live_tokens, model)``: every live token's K and V of
  every FULL layer, once; ``window_decode_kv_bytes(window_tokens, model)``:
  the same over the WINDOW layers for the tokens a row still sees there
  (``window_tokens``: the engine's ``decode_window_tokens`` a decode, the sum
  over live rows of ``min(context, window)``); ``moe_decode_bytes(touched,
  model)`` / ``moe_chunk_bytes``: every expert layer's router and shared
  expert, and an expert for every held expert that at least one row chose
  (``touched``: the program's own count a step, ``stats()["moe"]``);
  ``moe_pair_flops(model)``: the products of one (row, expert) pair.
"""

SERVE_MODEL = "afmoe"


def model_config(sizes: dict):
    from ray_tpu.models.afmoe import AfmoeConfig

    s = sizes
    assert s["model_type"] == "afmoe" and s["hidden_act"] == "silu", s
    assert s["score_func"] == "sigmoid" and s["route_norm"] and s["mup_enabled"], s
    assert s["n_group"] == 1 and s["topk_group"] == 1, s  # no group limit
    assert s["num_expert_groups"] == 1 and s["num_limited_groups"] == 1, s
    assert not s["tie_word_embeddings"] and s["rope_scaling"] is None, s
    assert s["num_shared_experts"] == 1, s
    every = s["global_attn_every_n_layers"]
    assert all(kind == ("full_attention" if (i + 1) % every == 0 else "sliding_attention")
               for i, kind in enumerate(s["layer_types"])), s
    dep, held = s["deployment"], s["num_experts"]
    assert held * dep["expert_parallel"] == dep["router_experts"], s
    first, n = dep["first_layer"], s["num_hidden_layers"]
    assert first + n <= len(s["layer_types"]), s
    return AfmoeConfig(
        vocab_size=s["vocab_size"], seq_len=s["max_position_embeddings"],
        d_model=s["hidden_size"], n_layers=n,
        layer_types=tuple(s["layer_types"][first:first + n]),
        n_dense_layers=s["num_dense_layers"],
        n_heads=s["num_attention_heads"], n_kv_heads=s["num_key_value_heads"],
        head_dim=s["head_dim"], window=s["sliding_window"], d_ff=s["intermediate_size"],
        d_expert=s["moe_intermediate_size"],
        n_routed_experts=dep["router_experts"], experts_held=held,
        expert_offset=dep["expert_offset"], expert_parallel=dep["expert_parallel"],
        experts_per_tok=s["num_experts_per_tok"], routed_scaling=float(s["route_scale"]),
        route_eps=s["route_eps"], norm_eps=s["rms_norm_eps"], rope_theta=float(s["rope_theta"]),
        init_range=s["init_range"], score_spread=s["attention_init"]["score_spread"],
        expert_out_gain=s["expert_init"]["out_gain"], dtype=s["dtype"],
    )


def program_init():
    from ray_tpu.models.afmoe import afmoe_init

    return afmoe_init


#: what the reference takes of the program's configuration, by its field names
_REFERENCE_FIELDS = (
    "d_model", "layer_types", "n_heads", "n_kv_heads", "head_dim", "window", "norm_eps",
    "rope_theta", "experts_per_tok", "expert_offset", "routed_scaling", "route_eps")


def reference_sizes(cfg) -> dict:
    return {k: getattr(cfg, k) for k in _REFERENCE_FIELDS}


def routing_margin(cfg) -> float:
    """What bf16 products upstream of the router leave undetermined; a float32
    program (the rehearsal) has no such products."""
    from benchmark.reference import afmoe as reference

    return reference.ROUTING_MARGIN if cfg.dtype == "bfloat16" else 0.0


def reference_logits(params, tokens, rows, cfg):
    import numpy as np

    from benchmark import harness as H
    from benchmark.reference import afmoe as reference

    margin = routing_margin(cfg)
    logits = np.asarray(reference.logits_at(params, tokens, rows, reference_sizes(cfg), margin))
    # a row the reference leaves undetermined is all zero: say how many the
    # harness's comparison is decided by
    H.emit("reference_rows", rows=len(rows), routing_margin=margin,
           undetermined=int((logits == 0).all(axis=-1).sum()))
    return logits


def _n_of(model: dict, kind: str) -> int:
    return list(model["layer_types"]).count(kind)


def _token_kv_bytes(model: dict) -> float:
    """A token's K and V in one layer, in the pool's dtype (2 bytes): 4,096 B
    at 8 heads of 128."""
    return 2 * model["n_kv_heads"] * model["head_dim"] * 2.0


def gqa_decode_kv_bytes(live_tokens: float, model: dict) -> float:
    """Bytes of K and V one decode step must read over the FULL attention
    layers: every live token's key and value of every key-value head, once.
    The window layers' are ``window_decode_kv_bytes``."""
    return live_tokens * _n_of(model, "full_attention") * _token_kv_bytes(model)


def window_decode_kv_bytes(window_tokens: float, model: dict) -> float:
    """The same over the WINDOW layers: ``window_tokens`` is the sum over the
    live rows of ``min(context, window)``, the keys a row's query still sees
    there (the engine's ``decode_window_tokens`` a decode)."""
    return window_tokens * _n_of(model, "sliding_attention") * _token_kv_bytes(model)


def moe_decode_bytes(touched: float, model: dict) -> float:
    """``touched``: held experts with at least one row, summed over the
    expert layers of ONE decode.  bfloat16 weights: an expert layer's router
    (1.57 MB, its float32 selection bias beside it) and shared expert (56.6 MB)
    always, 56.6 MB a touched expert."""
    d = model["d_model"]
    expert = 3 * d * model["d_expert"] * 2
    router = d * model["n_routed_experts"] * 2 + model["n_routed_experts"] * 4
    layers = model["n_layers"] - model["n_dense_layers"]
    return layers * (router + expert) + touched * expert


def moe_chunk_bytes(touched: float, model: dict) -> float:
    """The same for ONE prefill chunk: a touched expert's weights once,
    however many tiles of pairs go through them."""
    return moe_decode_bytes(touched, model)


def moe_pair_flops(model: dict) -> float:
    """The products of one (row, expert) pair: gate, up and down, 2 x d x f
    each."""
    return 6.0 * model["d_model"] * model["d_expert"]
