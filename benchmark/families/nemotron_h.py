"""Family ``nemotron_h``: nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16's
``config.json`` keys (``model_type`` ``nemotron_h``) onto
``ray_tpu.models.nemotron_h``; plain reference
``benchmark/reference/nemotron_h.py``.

The family's pieces, all found by name (nothing the benchmark had is
edited):

* ``model_config`` reads the published keys and refuses a file whose other
  published keys say something the program does not do (a bias on a
  projection, a convolution without bias, a tied head, another activation, an
  unnormalised top-k, a router limited to groups of experts, a sliding
  window).  The file's ``n_routed_experts`` is what THIS CHIP holds (under
  ``reduced``); the router's published width and the chip's place among those
  that share a layer stand in the file's ``deployment`` group
  (``router_experts``, ``expert_parallel``, ``expert_offset``).  What the
  published config does NOT give stands in the file's ``init_range``,
  ``route_eps``, ``ssm_init``, ``attention_init``, ``expert_init`` and
  ``state_dtype`` and is explained under its ``assumed``.
* ``reference_logits``: the equations over the whole sequence in float32
  (``reference/nemotron_h.py``): a token loop for the recurrence, a dense
  masked softmax, a loop over the held experts, no cache.  The program serves
  chunks (the SSD chunk form, a walk over the block table, the grouped expert
  kernel) and then decodes through a slot of state in the Mamba layers and
  paged K/V in the attention layers (the batch expert kernel), so the
  comparison that decides ``correct`` holds one to the other.
* ``expert_layer_deviation``: the ROUTED part of the program's expert layer
  (its norm, ``ops.moe.route``, ``held_pairs`` and ``expert_layer``: the
  grouped kernel on a chunk's 512 rows, the batch kernel on a decode's 16)
  against the reference's per-expert loop LAYER BY LAYER on the reference's
  own stream; ``reference_logits`` runs it on every probe sequence and ends
  the reference check where a layer stands further than
  ``EXPERT_LAYER_TOLERANCE``.  The logits cannot hold the routed experts'
  matrices to their precision (at 3 bits of mantissa they read 0.164 beside
  0.099-0.148 sound: the configuration's ``correctness``); on the same input,
  with the shared expert left out, nothing is amplified and nothing flips.
* the counts the roofline readers use, all of what the MATHEMATICS moves at
  the PUBLISHED widths, unpadded, so a share of them cannot pass 100%: an
  expert is TWO matrices.  ``moe_decode_bytes(touched, model)`` and
  ``moe_chunk_bytes``: every expert layer's router and shared expert, and an
  expert for every held expert that at least one row chose (``touched``: the
  program's own count a step, ``stats()["moe"]``); ``moe_pair_flops(model)``:
  the products of one (row, expert) pair;
  ``ssd_decode_state_bytes(live_rows, model)``: every live row's SSD state of
  every MAMBA layer and head, ``P x N`` float32, read once and written once;
  ``gqa_decode_kv_bytes(live_tokens, model)``: every live token's K and V of
  every ATTENTION layer, once.
"""

import functools

SERVE_MODEL = "nemotron_h"


def model_config(sizes: dict):
    from ray_tpu.models.nemotron_h import NemotronHConfig

    s = sizes
    assert s["model_type"] == "nemotron_h" and s["mlp_hidden_act"] == "relu2", s
    assert s["mamba_hidden_act"] == "silu" and s["use_conv_bias"], s
    assert not (s["attention_bias"] or s["mamba_proj_bias"] or s["mlp_bias"] or s["use_bias"]), s
    assert not s["tie_word_embeddings"] and s["sliding_window"] is None, s
    assert s["norm_topk_prob"] and s["n_group"] == 1 and s["topk_group"] == 1, s
    assert s["n_shared_experts"] == 1 and len(s["hybrid_override_pattern"]) == s[
        "num_hidden_layers"], s
    assert s["intermediate_size"] == s["moe_intermediate_size"], s
    assert s["layer_norm_epsilon"] == s["norm_eps"], s
    init, dep = dict(s["ssm_init"], **s["attention_init"], **s["expert_init"]), s["deployment"]
    assert s["n_routed_experts"] * dep["expert_parallel"] == dep["router_experts"], s
    assert (init["dt_min"], init["dt_max"]) == (s["time_step_min"], s["time_step_max"]), s
    return NemotronHConfig(
        vocab_size=s["vocab_size"], seq_len=s["max_position_embeddings"],
        d_model=s["hidden_size"], n_layers=s["num_hidden_layers"],
        pattern=s["hybrid_override_pattern"],
        n_heads=s["num_attention_heads"], n_kv_heads=s["num_key_value_heads"],
        head_dim=s["head_dim"], ssm_heads=s["mamba_num_heads"],
        ssm_head_dim=s["mamba_head_dim"], d_state=s["ssm_state_size"], n_groups=s["n_groups"],
        d_conv=s["conv_kernel"], ssm_chunk=s["chunk_size"],
        d_expert=s["moe_intermediate_size"], d_shared=s["moe_shared_expert_intermediate_size"],
        n_routed_experts=dep["router_experts"], experts_held=s["n_routed_experts"],
        expert_offset=dep["expert_offset"], expert_parallel=dep["expert_parallel"],
        experts_per_tok=s["num_experts_per_tok"],
        routed_scaling=float(s["routed_scaling_factor"]), route_eps=s["route_eps"],
        norm_eps=s["norm_eps"], init_range=s["init_range"],
        score_spread=init["score_spread"], attn_out_gain=init["out_gain"],
        expert_out_gain=init["routed_out_gain"], expert_lanes=s["expert_storage"]["lanes"],
        a_min=init["a_min"], a_max=init["a_max"], dt_min=init["dt_min"], dt_max=init["dt_max"],
        state_dtype=s["state_dtype"], dtype=s["dtype"],
    )


def program_init():
    from ray_tpu.models.nemotron_h import nemotron_h_init

    return nemotron_h_init


#: what the reference takes of the program's configuration, by its field names
_REFERENCE_FIELDS = (
    "n_heads", "n_kv_heads", "head_dim", "ssm_heads", "n_groups", "d_state", "d_conv",
    "norm_eps", "experts_per_tok", "expert_offset", "routed_scaling", "route_eps")


def reference_sizes(cfg) -> dict:
    return {k: getattr(cfg, k) for k in _REFERENCE_FIELDS}


#: rows of the expert-layer probe: a prefill chunk's (more than
#: ``ops.moe.TILE`` rows take the grouped form) and a decode batch's (the batch
#: form): the engine's ``prefill_chunk`` and ``max_slots``
PROBE_CHUNK, PROBE_BATCH = 512, 16
#: the largest relative rms the routed part of a layer's program may stand
#: from the reference's loop on the same input: between 0.00265 (the configured
#: programs, the largest of 23 layers x 2 forms x 4 probes on the chip) and
#: 0.0398 (the routed experts' matrices ALONE at 3 bits of mantissa, the
#: SMALLEST): 3.0 times of room above the one, 5.0 under the other, and the
#: limit LFM2's probe has; the configuration's ``correctness`` has every reading
EXPERT_LAYER_TOLERANCE = 0.008
#: a probe row whose held boundary pair lies this close (in selection score)
#: to a flip is left out: the program's router and the reference's are both
#: float32 at the highest precision and stand about 1e-7 apart
_PROBE_GAP = 1e-4


def probe_rows(first: int, last: int):
    """(the positions the probe reads, how many of them are the chunk's): the
    prompt's last chunk, which ends at ``first`` (the first compared row: the
    prompt's last token), and a decode batch's worth ending at ``last``."""
    import numpy as np

    chunk = np.arange(max(first + 1 - PROBE_CHUNK, 0), first + 1)
    return np.concatenate([chunk, np.arange(max(last + 1 - PROBE_BATCH, 0), last + 1)]), len(chunk)


@functools.lru_cache(maxsize=None)
def _routed_forms(cfg) -> dict:
    """The routed part of ``cfg``'s expert layer as the served steps run it
    (``NemotronHBody._routed``), jitted once a configuration, counted as a
    chunk's rows and as a decode's."""
    import jax
    import jax.numpy as jnp

    body = cfg.serving_body()
    ledger = body.counters()[0]
    counts = jnp.zeros(ledger.shape, ledger.dtype)

    def form(phase):
        return jax.jit(lambda h, layer, experts, index: body._routed(
            h, layer, jnp.ones((h.shape[0],), bool), counts, phase, experts, index)[1])

    return {"chunk": form("chunk"), "decode": form("decode")}


def expert_layer_deviation(cfg, params, taps: dict, chunk: int) -> list:
    """A dict an expert layer: the relative rms between what the ROUTED part
    of ``cfg``'s expert layer over ``params`` adds to the stream that entered
    the REFERENCE's layer and what the reference's loop added, as a ``chunk``
    (the first ``chunk`` tapped rows) and as a ``decode`` batch (the others)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    forms = _routed_forms(cfg)
    layers = [jax.tree_util.tree_map(lambda a, i=i: a[i], {k: run[k] for k in ("ln2", "router")})
              for run in params["runs"] if "router" in run
              for i in range(run["ln2"]["scale"].shape[0])]
    out = []
    for index, (layer, (h, want, gap)) in enumerate(zip(layers, taps["layers"])):
        line = {"layer": index}
        for phase, rows in (("chunk", slice(0, chunk)), ("decode", slice(chunk, None))):
            got = np.asarray(forms[phase](h[rows], layer, params["experts"], jnp.int32(index)))
            keep = gap[rows] >= _PROBE_GAP
            line[phase] = float(np.linalg.norm((got - want[rows])[keep])
                                / max(np.linalg.norm(want[rows][keep]), 1e-30))
            line[f"{phase}_rows"] = int(keep.sum())
        out.append(line)
    return out


def reference_logits(params, tokens, rows, cfg):
    import numpy as np

    from benchmark import harness as H
    from benchmark.reference import nemotron_h as reference

    # every row is compared (the configuration's correctness.routing_flips
    # says why a flipped choice needs no margin here)
    at, chunk = probe_rows(min(rows), max(rows))
    taps = {"rows": at}
    logits = np.asarray(reference.logits_at(params, tokens, rows, reference_sizes(cfg), taps))
    # the routed experts of every probe sequence, each layer against the
    # reference's loop on the reference's own stream: a prompt of 512 tokens
    # or more gives the grouped form a whole chunk
    layers = expert_layer_deviation(cfg, params, taps, chunk)
    worst = max(max(x["chunk"], x["decode"]) for x in layers)
    H.emit("expert_layer_probe", worst=worst, tolerance=EXPERT_LAYER_TOLERANCE, layers=layers)
    H.check(worst <= EXPERT_LAYER_TOLERANCE,
            f"the routed part of the program's expert layer stands {worst:.4f} (relative "
            f"rms) from the reference's loop on the same input; the limit is "
            f"{EXPERT_LAYER_TOLERANCE}")
    return logits


def _n_of(model: dict, kind: str) -> int:
    return list(model["layer_types"]).count(kind)


def moe_decode_bytes(touched: float, model: dict) -> float:
    """``touched``: held experts with at least one row, summed over the
    expert layers of ONE step.  bfloat16 weights, TWO matrices an expert: an
    expert layer's router (0.69 MB, its float32 selection bias beside it) and
    shared expert (39.9 MB) always, 19.96 MB a touched expert."""
    d, wide = model["d_model"], model["n_routed_experts"]
    always = d * wide * 2 + wide * 4 + 2 * d * model["d_shared"] * 2
    return _n_of(model, "moe") * always + touched * 2 * d * model["d_expert"] * 2


def moe_chunk_bytes(touched: float, model: dict) -> float:
    """The same for ONE prefill chunk: a touched expert's weights once,
    however many pairs go through them."""
    return moe_decode_bytes(touched, model)


def moe_pair_flops(model: dict) -> float:
    """The products of one (row, expert) pair: up and down, 2 x d x f each."""
    return 4.0 * model["d_model"] * model["d_expert"]


def ssd_decode_state_bytes(live_rows: float, model: dict) -> float:
    """Bytes of SSD state one decode step must move over the MAMBA layers:
    each live row's state of each head (``H x P x N`` float32: 2,097,152 B a
    layer), read once and written once."""
    state = model["ssm_heads"] * model["ssm_head_dim"] * model["d_state"] * 4
    return live_rows * _n_of(model, "mamba") * state * 2.0


def gqa_decode_kv_bytes(live_tokens: float, model: dict) -> float:
    """Bytes of K and V one decode step must read over the ATTENTION layers:
    every live token's key and value of every key-value head, once, in the
    pool's dtype (2 bytes): 1,024 B a token a layer at 2 heads of 128."""
    return (live_tokens * _n_of(model, "attention") * 2 * model["n_kv_heads"]
            * model["head_dim"] * 2.0)
