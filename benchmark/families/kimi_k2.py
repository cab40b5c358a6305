"""Family ``kimi_k2``: moonshotai/Kimi-K2.5's ``config.json`` keys (the
language model's; ``model_type`` ``kimi_k2``, DeepSeek-V3's block) onto
``ray_tpu.models.kimi_k2``; plain reference ``benchmark/reference/kimi_k2.py``.

The family's pieces, all found by name (nothing the benchmark had is
edited):

* ``model_config`` reads the published keys and refuses a file whose other
  published keys say something the program does not do (a bias in the
  attention, another activation, scoring or selection rule, a limit on
  expert groups, a tied head, extra prediction layers).  The file's
  ``n_routed_experts`` and ``vocab_size`` are what THIS CHIP holds (both
  under ``reduced``); the router's published width and the chip's place among
  those that share a layer stand in the file's ``deployment`` group
  (``router_experts``, ``expert_parallel``, ``expert_offset``).
* ``reference_logits``: the equations over the whole sequence in float32
  (``reference/kimi_k2.py``): expanded attention, a loop over the held
  experts, no cache.  The program serves chunks, block tables, the absorbed
  form and tiles of pairs, so the comparison that decides ``correct`` holds
  one to the other.
* ``latent_decode_bytes`` / ``latent_decode_flops``: what the mathematics of
  ONE decode's latent attention reads and computes: every live token's row
  (``kv_lora_rank + qk_rope_head_dim`` numbers, UNPADDED, a shared block
  counted once for every row that reads it) in every layer; every head's
  score against the row and its weighted sum of the row's latent part.
* ``moe_decode_bytes``: the weights ONE decode's expert layers read: a
  layer's router and shared expert, and an expert for every held expert
  that at least one row chose (``touched``: the program's own count a
  decode, ``stats()["moe"]``).
"""

SERVE_MODEL = "kimi_k2"


def model_config(sizes: dict):
    from ray_tpu.models.kimi_k2 import KimiK2Config

    assert sizes["hidden_act"] == "silu" and not sizes["attention_bias"], sizes
    assert sizes["scoring_func"] == "sigmoid" and sizes["topk_method"] == "noaux_tc", sizes
    assert sizes["n_group"] == 1 and sizes["topk_group"] == 1, sizes  # no group limit
    assert sizes["norm_topk_prob"] and not sizes["tie_word_embeddings"], sizes
    assert sizes["moe_layer_freq"] == 1 and sizes["num_nextn_predict_layers"] == 0, sizes
    assert sizes["num_key_value_heads"] == sizes["num_attention_heads"], sizes
    rope, dep = sizes["rope_scaling"], sizes["deployment"]
    assert rope["type"] == "yarn", rope
    return KimiK2Config(
        vocab_size=sizes["vocab_size"], seq_len=sizes["max_position_embeddings"],
        d_model=sizes["hidden_size"], n_layers=sizes["num_hidden_layers"],
        n_dense_layers=sizes["first_k_dense_replace"], n_heads=sizes["num_attention_heads"],
        q_lora_rank=sizes["q_lora_rank"], kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"], qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"], d_ff=sizes["intermediate_size"],
        d_expert=sizes["moe_intermediate_size"],
        n_routed_experts=dep["router_experts"], experts_held=sizes["n_routed_experts"],
        expert_offset=dep["expert_offset"], expert_parallel=dep["expert_parallel"],
        experts_per_tok=sizes["num_experts_per_tok"], n_shared_experts=sizes["n_shared_experts"],
        routed_scaling_factor=sizes["routed_scaling_factor"], rms_norm_eps=sizes["rms_norm_eps"],
        rope_theta=float(sizes["rope_theta"]), rope_factor=float(rope["factor"]),
        rope_original_max_position=rope["original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]), rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]), rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        init_range=sizes["init_range"], dtype=sizes["dtype"],
    )


def program_init():
    from ray_tpu.models.kimi_k2 import kimi_k2_init

    return kimi_k2_init


def reference_sizes(cfg) -> dict:
    """The reference's own arguments from the program's configuration: the
    frequencies and the scale are computed by the REFERENCE's functions."""
    from benchmark.reference import kimi_k2 as reference

    return dict(
        n_heads=cfg.n_heads, nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
        eps=cfg.rms_norm_eps,
        scale=reference.softmax_scale(cfg.head_dim, cfg.rope_factor, cfg.rope_mscale_all_dim),
        inv_freq=reference.yarn_inv_freq(
            cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
            cfg.rope_original_max_position, cfg.rope_beta_fast, cfg.rope_beta_slow),
        top_k=cfg.experts_per_tok, scaling=cfg.routed_scaling_factor,
        offset=cfg.expert_offset,
    )


def reference_logits(params, tokens, rows, cfg):
    import numpy as np

    from benchmark import harness as H
    from benchmark.reference import kimi_k2 as reference

    assert cfg.rope_mscale == cfg.rope_mscale_all_dim, "the reference rotates at scale 1"
    # what bf16 products upstream of the router leave undetermined; a float32
    # program (the rehearsal) has no such products
    margin = reference.ROUTING_MARGIN if cfg.dtype == "bfloat16" else 0.0
    logits = np.asarray(
        reference.logits_at(params, tokens, rows, margin, **reference_sizes(cfg)))
    # a row the reference leaves undetermined is all zero: say how many the
    # harness's comparison is decided by
    H.emit("reference_rows", rows=len(rows), routing_margin=margin,
           undetermined=int((logits == 0).all(axis=-1).sum()))
    return logits


def _row_numbers(model: dict) -> int:
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def latent_decode_bytes(live_tokens: float, model: dict) -> float:
    """``live_tokens``: the tokens of context over all live rows; every
    layer reads each one's row once, in bfloat16 (1,152 B at the published
    sizes)."""
    return model["n_layers"] * live_tokens * _row_numbers(model) * 2


def latent_decode_flops(live_tokens: float, model: dict) -> float:
    """A head's score against a row (576 multiply-adds) and its share of the
    row's latent part in the output (512), every head, every layer."""
    per_pair = 2 * (_row_numbers(model) + model["kv_lora_rank"])
    return model["n_layers"] * live_tokens * model["n_heads"] * per_pair


def moe_decode_bytes(touched: float, model: dict) -> float:
    """``touched``: held experts with at least one row, summed over the
    expert layers of ONE decode.  bfloat16 weights."""
    d, f = model["d_model"], model["d_expert"]
    layers = model["n_layers"] - model["n_dense_layers"]
    expert = 3 * d * f * 2
    always = d * model["n_routed_experts"] * 2 + model["n_shared_experts"] * expert
    return layers * always + touched * expert
