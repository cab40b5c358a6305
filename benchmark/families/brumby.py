"""Family ``brumby``: manifestai/Brumby-14B-Base's ``config.json`` keys onto
``ray_tpu.models.brumby``; plain reference ``benchmark/reference/brumby.py``.

The family's pieces, all found by name (nothing the benchmark had is
edited):

* ``model_config`` reads the published keys (a Qwen3-shaped config:
  ``hidden_size``, ``num_attention_heads`` over ``num_key_value_heads`` of
  ``head_dim``, ``intermediate_size``, ``rms_norm_eps``, ``rope_theta``,
  ``max_position_embeddings``) and refuses a file whose other published keys
  say something the program does not do (a bias, tied embeddings, a sliding
  window, rope scaling).  What the published config does NOT give, the
  retention's own sizes, stands in the file's ``retention`` group and is
  explained under its ``assumed``: ``power``, ``eps``, ``gate_shift``,
  ``state_dtype``.
* ``reference_logits`` is the ATTENTION form in float32
  (``reference/brumby.py``); the program serves the recurrent form, so the
  comparison that decides ``correct`` holds one form to the other.  A
  configuration of this family names probe prompts long enough to cross many
  prefill chunks: the state is then carried, chunk to chunk and through
  every decode, for the whole probe.
* ``retention_decode_state_bytes`` is what ``retention_decode_roofline``
  divides by the chip's bandwidth: every live row's state of every layer
  and key-value head, ``d (d + 1) / 2`` features by ``d + 1`` value rows in
  float32, read once and written once.  It counts what the mathematics
  moves: not the tile padding of the state on the device (8320 x 136 for
  8256 x 129 at ``d = 128``), not the feature vectors, so a share of it
  cannot pass 100%.
* the per-layer readers this family adds read the device scopes
  ``retention`` (``retention_decode_dev_ms``, ``retention_decode_roofline``)
  and the counters ``stats()["state_pool"]``: ``decodes`` and
  ``decode_rows`` give the live rows of the decodes in the traced slice.
"""

SERVE_MODEL = "brumby"


def model_config(sizes: dict):
    from ray_tpu.models.brumby import BrumbyConfig

    assert sizes["hidden_act"] == "silu" and not sizes["attention_bias"], sizes
    assert not sizes["tie_word_embeddings"] and sizes["rope_scaling"] is None, sizes
    assert sizes["sliding_window"] is None and not sizes["use_sliding_window"], sizes
    ret = sizes["retention"]
    return BrumbyConfig(
        vocab_size=sizes["vocab_size"], seq_len=sizes["max_position_embeddings"],
        d_model=sizes["hidden_size"], n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"], n_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], d_ff=sizes["intermediate_size"],
        rms_eps=sizes["rms_norm_eps"], rope_theta=float(sizes["rope_theta"]),
        power=ret["power"], retention_eps=ret["eps"], gate_shift=ret["gate_shift"],
        state_dtype=ret["state_dtype"], dtype=sizes["dtype"],
    )


def program_init():
    from ray_tpu.models.brumby import brumby_init

    return brumby_init


def reference_logits(params, tokens, rows, cfg):
    from benchmark.reference import brumby as reference

    return reference.logits_at(
        params, tokens, rows, cfg.n_heads, cfg.n_kv_heads, cfg.rms_eps,
        cfg.rope_theta, cfg.gate_shift, cfg.retention_eps)


def retention_decode_state_bytes(live_rows: float, model: dict) -> float:
    """Bytes of recurrent state one decode step must move over all layers:
    each live row's state of each key-value head, read once and written
    once, float32, unpadded."""
    d = model["head_dim"]
    state = d * (d + 1) // 2 * (d + 1) * 4
    return live_rows * model["n_layers"] * model["n_kv_heads"] * state * 2.0
