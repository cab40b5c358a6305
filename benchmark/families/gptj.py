"""Family ``gptj``: EleutherAI/gpt-j-6b's ``config.json`` keys onto
``ray_tpu.models.gptj``; plain reference ``benchmark/reference/gptj.py``."""

from benchmark.costs import paged_decode_kv_bytes  # noqa: F401

SERVE_MODEL = "gptj"


def model_config(sizes: dict):
    from ray_tpu.models.gptj import GPTJConfig

    # n_inner null = 4 x n_embd, which is what GPTJConfig.d_ff computes
    assert sizes.get("n_inner") in (None, 4 * sizes["n_embd"]), sizes.get("n_inner")
    return GPTJConfig(
        vocab_size=sizes["vocab_size"], seq_len=sizes["n_positions"],
        d_model=sizes["n_embd"], n_layers=sizes["n_layer"], n_heads=sizes["n_head"],
        rotary_dim=sizes["rotary_dim"], dtype=sizes["dtype"],
    )


def program_init():
    from ray_tpu.models.gptj import gptj_init

    return gptj_init


def reference_logits(params, tokens, rows, cfg):
    from benchmark.reference import gptj as reference

    return reference.logits_at(params, tokens, rows, cfg.n_heads, cfg.rotary_dim)
