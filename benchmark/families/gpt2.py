"""Family ``gpt2``: openai-community/gpt2*'s ``config.json`` keys onto
``ray_tpu.models.gpt``; plain reference ``benchmark/reference/gpt2.py``."""

from benchmark.costs import flash_train_flops  # noqa: F401
from benchmark.costs import gpt2_train_flops_per_token as train_flops_per_token  # noqa: F401


def model_config(sizes: dict):
    from ray_tpu.models.gpt import GPTConfig

    assert sizes.get("n_inner") in (None, 4 * sizes["n_embd"]), sizes.get("n_inner")
    return GPTConfig(
        vocab_size=sizes["vocab_size"], seq_len=sizes["n_positions"],
        d_model=sizes["n_embd"], n_layers=sizes["n_layer"], n_heads=sizes["n_head"],
        dtype=sizes["dtype"], **sizes.get("model_options", {}),
    )


def program_init():
    from ray_tpu.models.gpt import gpt_init

    return gpt_init


def loss(cfg, params, tokens, mesh):
    from ray_tpu.models.gpt import gpt_loss

    return gpt_loss(cfg, params, tokens, mesh)


def reference_loss(params, tokens, cfg):
    from benchmark.reference import gpt2 as reference

    return reference.loss(params, tokens, cfg.n_heads)
