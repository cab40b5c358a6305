"""Family ``phi4flash``: microsoft/Phi-4-mini-flash-reasoning's ``config.json``
keys onto ``ray_tpu.models.phi4flash``; plain reference
``benchmark/reference/phi4flash.py``.

The family's pieces, all found by name (nothing the benchmark had is
edited):

* ``model_config`` reads the published keys (``hidden_size``,
  ``num_attention_heads`` over ``num_key_value_heads``, ``intermediate_size``,
  ``num_hidden_layers``, ``sliding_window``, ``layer_norm_eps``,
  ``mb_per_layer``, ``vocab_size``, ``max_position_embeddings``) and refuses
  a file whose other published keys say something the program does not do (an
  untied head, a bias in the MLP or on the head, dropout, another
  activation).  What the published config does NOT give stands in the file's
  ``state_space`` and ``attention`` groups and is explained under its
  ``assumed``.
* ``reference_logits``: the equations over the whole sequence in float32
  (``reference/phi4flash.py``): a plain loop for the scan, dense masked
  softmaxes, no cache.  The program serves chunks, rings and block tables, so
  the comparison that decides ``correct`` holds one to the other.  A
  configuration of this family names probe prompts that cross several chunks
  AND the window.
* ``shared_kv_decode_bytes``: what the mathematics reads of the shared K/V
  in ONE decode (the full-attention layer and every cross layer read each
  live token's K and V of the ONE shared layer), unpadded, so a share of it
  cannot pass 100%.  The scan and the rings get their counts with their
  kernels (PERF.md, section 3, has the formulas).
* the per-layer readers this family adds read the device scopes ``ssm``,
  ``window_attention`` and ``shared_kv_attention`` and the counters
  ``stats()["state_pool"]``: ``decodes``, ``decode_rows`` and
  ``decode_tokens`` give the live rows and the live shared-K/V tokens of the
  decodes in the traced slice.
"""

SERVE_MODEL = "phi4flash"


def model_config(sizes: dict):
    from ray_tpu.models.phi4flash import Phi4FlashConfig

    assert sizes["hidden_act"] == "silu" and sizes["tie_word_embeddings"], sizes
    assert not sizes["mlp_bias"] and not sizes["lm_head_bias"], sizes
    assert sizes["embd_pdrop"] == 0 and sizes["resid_pdrop"] == 0, sizes
    assert sizes["mb_per_layer"] == 2, sizes  # a state-space layer every other layer
    ssm, att = sizes["state_space"], sizes["attention"]
    return Phi4FlashConfig(
        vocab_size=sizes["vocab_size"], seq_len=sizes["max_position_embeddings"],
        d_model=sizes["hidden_size"], n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"], n_kv_heads=sizes["num_key_value_heads"],
        d_ff=sizes["intermediate_size"], sliding_window=sizes["sliding_window"],
        layer_norm_eps=sizes["layer_norm_eps"],
        d_inner=ssm["d_inner"], d_state=ssm["d_state"], d_conv=ssm["d_conv"],
        dt_rank=ssm["dt_rank"], dt_min=ssm["dt_min"], dt_max=ssm["dt_max"],
        state_dtype=ssm["state_dtype"], subln_eps=att["subln_eps"],
        init_range=sizes["init_range"], dtype=sizes["dtype"],
    )


def program_init():
    from ray_tpu.models.phi4flash import phi4flash_init

    return phi4flash_init


def reference_logits(params, tokens, rows, cfg):
    from benchmark.reference import phi4flash as reference

    return reference.logits_at(
        params, tokens, rows, cfg.n_heads, cfg.n_kv_heads, cfg.sliding_window,
        cfg.layer_norm_eps, cfg.subln_eps)


def _kv_token_bytes(model: dict) -> int:
    """K and V of one token in one layer: K heads of e, twice, in bfloat16
    (5,120 B at the published widths)."""
    return model["n_kv_heads"] * (model["d_model"] // model["n_heads"]) * 2 * 2


def shared_kv_decode_bytes(live_tokens: float, model: dict) -> float:
    """``live_tokens``: the tokens of context over all live rows; the
    readers are the full-attention layer and the cross layers above it."""
    readers = 1 + (model["n_layers"] - model["n_layers"] // 2 - 2) // 2
    return readers * live_tokens * _kv_token_bytes(model)
