"""Family ``granite_h``: ibm-granite/granite-4.0-h-small's ``config.json``
keys (``model_type`` ``granitemoehybrid``) onto ``ray_tpu.models.granite_h``;
plain reference ``benchmark/reference/granite_h.py``.

The family's pieces, all found by name (nothing the benchmark had is
edited):

* ``model_config`` reads the published keys and refuses a file whose other
  published keys say something the program does not do (a bias on a
  projection, a convolution without bias, an untied head, another
  activation, norm or positional encoding, a rope scaling, an expansion that
  is not heads x head size).  The file's ``num_local_experts`` and
  ``vocab_size`` are what THIS CHIP holds, and of the published
  ``layer_types`` (kept whole) the first ``num_hidden_layers`` run here (all
  three under ``reduced``); the router's published width and the chip's
  place among those that share a layer stand in the file's ``deployment``
  group (``router_experts``, ``expert_parallel``, ``expert_offset``).  What
  the published config does NOT give stands in the file's ``init_range``,
  ``ssm_init`` and ``attention_init`` groups and ``state_dtype`` and is
  explained under its ``assumed``.
* ``reference_logits``: the equations over the whole sequence in float32
  (``reference/granite_h.py``): a token loop for the recurrence, a dense
  masked softmax, a loop over the held experts, no cache.  The program serves
  chunks (the SSD chunk form, a walk over the block table, tiles of pairs)
  and then decodes through a slot of state in the Mamba layers and paged K/V
  in the attention layers, so the comparison that decides ``correct`` holds
  one to the other.  A configuration of this family names probe prompts that
  cross a chunk AND a sub-chunk boundary.
* the counts the roofline readers use, all of what the MATHEMATICS moves,
  unpadded, so a share of them cannot pass 100%:
  ``moe_decode_bytes(touched, model)``: every layer's router and shared MLP,
  and an expert for every held expert that at least one row chose
  (``touched``: the program's own count a decode, ``stats()["moe"]``);
  ``ssd_decode_state_bytes(live_rows, model)``: every live row's SSD state of
  every MAMBA layer and head, ``P x N`` float32, read once and written once;
  ``gqa_decode_kv_bytes(live_tokens, model)``: every live token's K and V of
  every ATTENTION layer, once.
"""

SERVE_MODEL = "granite_h"


def model_config(sizes: dict):
    from ray_tpu.models.granite_h import GraniteHConfig

    s = sizes
    assert s["model_type"] == "granitemoehybrid" and s["hidden_act"] == "silu", s
    assert not (s["attention_bias"] or s["mamba_proj_bias"]) and s["mamba_conv_bias"], s
    assert s["tie_word_embeddings"] and s["rope_scaling"] is None, s
    assert s["position_embedding_type"] == "nope", s  # rope_theta is then unused
    assert s["normalization_function"] == "rmsnorm", s
    assert s["mamba_expand"] * s["hidden_size"] == s["mamba_n_heads"] * s["mamba_d_head"], s
    # the file keeps the published pattern whole; the layers run here are its
    # first ``num_hidden_layers``
    assert len(s["layer_types"]) >= s["num_hidden_layers"], s
    init, dep = dict(s["ssm_init"], **s["attention_init"]), s["deployment"]
    return GraniteHConfig(
        vocab_size=s["vocab_size"], seq_len=s["max_position_embeddings"],
        d_model=s["hidden_size"], n_layers=s["num_hidden_layers"],
        layer_types=tuple(s["layer_types"][:s["num_hidden_layers"]]),
        n_heads=s["num_attention_heads"], n_kv_heads=s["num_key_value_heads"],
        head_dim=s["hidden_size"] // s["num_attention_heads"],
        d_ssm=s["mamba_n_heads"] * s["mamba_d_head"], ssm_heads=s["mamba_n_heads"],
        d_state=s["mamba_d_state"], n_groups=s["mamba_n_groups"], d_conv=s["mamba_d_conv"],
        ssm_chunk=s["mamba_chunk_size"],
        d_expert=s["intermediate_size"], d_shared=s["shared_intermediate_size"],
        n_routed_experts=dep["router_experts"], experts_held=s["num_local_experts"],
        expert_offset=dep["expert_offset"], expert_parallel=dep["expert_parallel"],
        experts_per_tok=s["num_experts_per_tok"], rms_norm_eps=s["rms_norm_eps"],
        embedding_multiplier=float(s["embedding_multiplier"]),
        residual_multiplier=s["residual_multiplier"],
        attention_multiplier=s["attention_multiplier"],
        logits_scaling=float(s["logits_scaling"]),
        init_range=s["init_range"], score_spread=init["score_spread"],
        attn_out_gain=init["out_gain"],
        a_min=init["a_min"], a_max=init["a_max"], dt_min=init["dt_min"], dt_max=init["dt_max"],
        state_dtype=s["state_dtype"], dtype=s["dtype"],
    )


def program_init():
    from ray_tpu.models.granite_h import granite_h_init

    return granite_h_init


#: what the reference takes of the program's configuration, by its field names
_REFERENCE_FIELDS = (
    "n_heads", "n_kv_heads", "head_dim", "ssm_heads", "n_groups", "d_state", "d_conv",
    "rms_norm_eps", "embedding_multiplier", "residual_multiplier", "attention_multiplier",
    "logits_scaling", "experts_per_tok", "expert_offset")


def reference_sizes(cfg) -> dict:
    return {k: getattr(cfg, k) for k in _REFERENCE_FIELDS}


def reference_logits(params, tokens, rows, cfg):
    import numpy as np

    from benchmark import harness as H
    from benchmark.reference import granite_h as reference

    logits = np.asarray(reference.logits_at(params, tokens, rows, reference_sizes(cfg)))
    # every row is compared (the configuration's correctness.routing_margin
    # says why a flipped choice needs no margin here): say so, run by run
    H.emit("reference_rows", rows=len(rows), routing_margin=0.0,
           undetermined=int((logits == 0).all(axis=-1).sum()))
    return logits


def _n_of(model: dict, kind: str) -> int:
    return list(model["layer_types"]).count(kind)


def moe_decode_bytes(touched: float, model: dict) -> float:
    """``touched``: held experts with at least one row, summed over the
    layers of ONE decode.  bfloat16 weights: a layer's router (0.59 MB) and
    shared MLP (37.75 MB) always, 18.87 MB a touched expert."""
    d = model["d_model"]
    always = d * model["n_routed_experts"] * 2 + 3 * d * model["d_shared"] * 2
    return model["n_layers"] * always + touched * 3 * d * model["d_expert"] * 2


def ssd_decode_state_bytes(live_rows: float, model: dict) -> float:
    """Bytes of SSD state one decode step must move over the MAMBA layers:
    each live row's state of each head (``d_ssm x d_state`` float32:
    4,194,304 B a layer), read once and written once."""
    return live_rows * _n_of(model, "mamba") * model["d_ssm"] * model["d_state"] * 4 * 2.0


def gqa_decode_kv_bytes(live_tokens: float, model: dict) -> float:
    """Bytes of K and V one decode step must read over the ATTENTION layers:
    every live token's key and value of every key-value head, once, in the
    pool's dtype (2 bytes): 4,096 B a token a layer at 8 heads of 128."""
    return (live_tokens * _n_of(model, "attention") * 2 * model["n_kv_heads"]
            * model["head_dim"] * 2.0)
