"""One module per model FAMILY, found by the ``family`` key of a
configuration's file (``harness.load_family``).  A family says how the
configuration's published keys become the program's model object, which
initializer, loss and served-model name the program has for it, which
plain reference (``benchmark/reference/``) answers for it, and which
operation and byte counts (``benchmark/costs.py``) its roofline readers
use.  The harness, the traffic kinds and the readers name no family: a
configuration of a new family is this one more file (and its reference).

What a family may define (a kind or reader that needs a missing piece
fails with the name of what is missing):

* ``model_config(sizes) -> object`` — the program's model configuration
  from the configuration file's keys as run (``harness.sizes``);
* ``program_init() -> callable`` — the program's ``init(key, cfg)``;
* serving: ``SERVE_MODEL`` (what ``build_llm_app(model=...)`` takes) and
  ``reference_logits(params, tokens, rows, cfg)``;
* training: ``loss(cfg, params, tokens, mesh)`` (the program's) and
  ``reference_loss(params, tokens, cfg)`` (the plain reference's);
* counts: ``train_flops_per_token(model)``, ``flash_train_flops(batch,
  model)``, ``paged_decode_kv_bytes(live_tokens, model, tp)`` — ``model``
  is ``dataclasses.asdict`` of the program's model configuration.
"""
