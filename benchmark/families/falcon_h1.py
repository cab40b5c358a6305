"""Family ``falcon_h1``: tiiuae/Falcon-H1-34B-Instruct's ``config.json`` keys
onto ``ray_tpu.models.falcon_h1``; plain reference
``benchmark/reference/falcon_h1.py``.

The family's pieces, all found by name (nothing the benchmark had is
edited):

* ``model_config`` reads the published keys (``hidden_size``,
  ``num_attention_heads`` over ``num_key_value_heads`` of ``head_dim``,
  ``intermediate_size``, the ``mamba_*`` sizes, ``rms_norm_eps``,
  ``rope_theta``, every multiplier) and refuses a file whose other published
  keys say something the program does not do: a bias on a projection, the
  MLP or the attention, tied embeddings, rope scaling, attention in some
  layers only, the norm before the gate, no gated norm, a convolution
  without bias, another activation, a ``mamba_d_ssm`` that is not heads x
  head size.  What the published config
  does NOT give stands in the file's ``ssm_init`` and ``attention_init``
  groups and ``state_dtype``
  and is explained under its ``assumed``.
* ``reference_logits``: the equations over the whole sequence in float32
  (``reference/falcon_h1.py``): a token loop for the recurrence, a dense
  masked softmax, no cache.  The program serves chunks (the SSD chunk form,
  a walk over the block table) and then decodes through paged K/V and a slot
  of state in every layer, so the comparison that decides ``correct`` holds
  one to the other.  A configuration of this family names probe prompts that
  cross chunk AND sub-chunk boundaries.
* the counts the roofline readers use, all of what the MATHEMATICS moves,
  unpadded, so a share of them cannot pass 100%:
  ``ssd_decode_state_bytes(live_rows, model)``: every live row's SSD state of
  every layer and head, ``P x N`` float32, read once and written once;
  ``gqa_decode_kv_bytes(live_tokens, model)``: every live token's K and V of
  every layer, once (the query heads that share a key-value head read it
  together).  The chunk form gets its counts, and an ``ssd_chunk_roofline``,
  with a kernel of its own (it is XLA einsums today).
* the per-layer readers this family adds read the device scopes ``ssm`` /
  ``ssd_update`` / ``ssd_chunk`` / ``gqa_attention`` / ``chunk_attention``
  (``layer_metrics/_inner_scope``) and the counters
  ``stats()["state_pool"]``: ``decodes``, ``decode_rows``, ``decode_tokens``,
  ``chunks``, ``chunk_tokens``, ``chunk_context_tokens``.
"""

SERVE_MODEL = "falcon_h1"


def model_config(sizes: dict):
    from ray_tpu.models.falcon_h1 import FalconH1Config

    s = sizes
    assert s["model_type"] == "falcon_h1" and s["hidden_act"] == "silu", s
    assert not (s["attention_bias"] or s["mlp_bias"] or s["projectors_bias"]
                or s["mamba_proj_bias"]), s
    assert not s["tie_word_embeddings"] and s["rope_scaling"] is None, s
    assert s["attn_layer_indices"] is None, s  # attention in EVERY layer
    assert s["mamba_rms_norm"] and not s["mamba_norm_before_gate"] and s["mamba_conv_bias"], s
    assert s["mamba_use_mlp"], s
    assert s["mamba_d_ssm"] == s["mamba_n_heads"] * s["mamba_d_head"], s
    init = dict(s["ssm_init"], **s["attention_init"])
    return FalconH1Config(
        vocab_size=s["vocab_size"], seq_len=s["max_position_embeddings"],
        d_model=s["hidden_size"], n_layers=s["num_hidden_layers"],
        n_heads=s["num_attention_heads"], n_kv_heads=s["num_key_value_heads"],
        head_dim=s["head_dim"], d_ff=s["intermediate_size"],
        d_ssm=s["mamba_d_ssm"], ssm_heads=s["mamba_n_heads"], d_state=s["mamba_d_state"],
        n_groups=s["mamba_n_groups"], d_conv=s["mamba_d_conv"], ssm_chunk=s["mamba_chunk_size"],
        rms_norm_eps=s["rms_norm_eps"], rope_theta=float(s["rope_theta"]),
        embedding_multiplier=s["embedding_multiplier"],
        lm_head_multiplier=s["lm_head_multiplier"],
        attention_in_multiplier=s["attention_in_multiplier"],
        attention_out_multiplier=s["attention_out_multiplier"],
        key_multiplier=s["key_multiplier"], ssm_in_multiplier=s["ssm_in_multiplier"],
        ssm_out_multiplier=s["ssm_out_multiplier"],
        ssm_multipliers=tuple(s["ssm_multipliers"]), mlp_multipliers=tuple(s["mlp_multipliers"]),
        score_spread=init["score_spread"], a_min=init["a_min"], a_max=init["a_max"], dt_min=init["dt_min"], dt_max=init["dt_max"],
        state_dtype=s["state_dtype"], dtype=s["dtype"],
    )


def program_init():
    from ray_tpu.models.falcon_h1 import falcon_h1_init

    return falcon_h1_init


#: what the reference takes of the program's configuration, by its field names
_REFERENCE_FIELDS = (
    "n_heads", "n_kv_heads", "head_dim", "ssm_heads", "n_groups", "d_state", "d_conv",
    "rms_norm_eps", "rope_theta", "embedding_multiplier", "lm_head_multiplier",
    "attention_in_multiplier", "attention_out_multiplier", "key_multiplier",
    "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")


def reference_logits(params, tokens, rows, cfg):
    from benchmark.reference import falcon_h1 as reference

    return reference.logits_at(
        params, tokens, rows, {k: getattr(cfg, k) for k in _REFERENCE_FIELDS})


def _state_bytes(model: dict) -> int:
    """One sequence's SSD state in one layer: H heads of P x N float32
    (4,194,304 B at the published sizes)."""
    return model["d_ssm"] * model["d_state"] * 4


def ssd_decode_state_bytes(live_rows: float, model: dict) -> float:
    """Bytes of SSD state one decode step must move over all layers: each
    live row's state of each head, read once and written once."""
    return live_rows * model["n_layers"] * _state_bytes(model) * 2.0


def gqa_decode_kv_bytes(live_tokens: float, model: dict) -> float:
    """Bytes of K and V one decode step must read over all layers: every
    live token's key and value of every key-value head, once, in the pool's
    dtype (2 bytes): 2,048 B a token a layer at 4 heads of 128."""
    return live_tokens * model["n_layers"] * 2 * model["n_kv_heads"] * model["head_dim"] * 2.0
