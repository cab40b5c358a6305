"""Family ``lfm2_moe``: LiquidAI/LFM2-24B-A2B's ``config.json`` keys
(``model_type`` ``lfm2_moe``) onto ``ray_tpu.models.lfm2``; plain reference
``benchmark/reference/lfm2_moe.py``.

The family's pieces, all found by name (nothing the benchmark had is edited):

* ``model_config`` reads the published keys and refuses a file whose other
  published keys say something the program does not do (a bias on the
  convolution, an unnormalised top-k, no selection bias, an untied head,
  another rope type).  Of the published ``layer_types`` (kept whole) the first
  ``num_hidden_layers`` run here; the chip's share of an expert layer stands in
  the file's ``deployment`` group (``router_experts``, ``expert_parallel``,
  ``expert_offset``: all 64 of 64 at the published deployment).  What the
  published config does NOT give stands in the file's ``tie_embedding``,
  ``route_eps``, ``init_range``, ``attention_init``, ``expert_init`` and
  ``conv_init`` and is explained under its ``assumed``.
* ``reference_logits``: the equations over the whole sequence in float32
  (``reference/lfm2_moe.py``): a token loop for the convolution, a dense masked
  softmax, a loop over the experts, no cache.  The program serves chunks (the
  convolution over a chunk with the slot's tails in, a walk over the block
  table, tiles of pairs) and then decodes through the tails and the paged K/V
  of two 64-wide heads a row, so the comparison that decides ``correct`` holds
  one to the other.
* ``expert_layer_deviation``: the program's expert layer in BOTH forms, at the
  engine's sizes, against the reference's loop LAYER BY LAYER on the
  reference's own stream; ``reference_logits`` runs it on every probe sequence and
  ends the reference check where a layer stands further than
  ``EXPERT_LAYER_TOLERANCE``.  The logits cannot hold the experts' matrices
  to their precision (the two leading layers' rounding, amplified by the eight
  after them, is four fifths of the noise in the logits and the experts'
  products a twentieth); on the same input nothing is amplified.
* the counts the roofline readers use, all of what the MATHEMATICS moves,
  unpadded, so a share of them cannot pass 100%:
  ``moe_decode_bytes(touched, model)`` and ``moe_chunk_bytes(touched,
  model)``: every expert layer's router, and an expert for every held expert
  that at least one row chose (``touched``: the program's own count a step,
  ``stats()["moe"]``; the dense MLPs are not the expert layer's);
  ``moe_pair_flops(model)``: the products of one (row, expert) pair;
  ``gqa_decode_kv_bytes(live_tokens, model)``: every live token's K and V of
  every ATTENTION layer, once; ``short_conv_decode_bytes(live_rows, model)``:
  every CONV layer's ``W_in``, taps and ``W_out`` once and each live row's
  tails read and written.
"""

import functools

SERVE_MODEL = "lfm2_moe"


def model_config(sizes: dict):
    from ray_tpu.models.lfm2 import Lfm2MoeConfig

    s = sizes
    assert s["model_type"] == "lfm2_moe" and not s["conv_bias"], s
    assert s["norm_topk_prob"] and s["use_expert_bias"] and s["tie_embedding"], s
    assert s["rope_parameters"]["rope_type"] == "default", s
    # the file keeps the published pattern whole; the layers run here are its
    # first ``num_hidden_layers``
    assert len(s["layer_types"]) >= s["num_hidden_layers"], s
    dep, held = s["deployment"], s["num_experts"]
    assert held * dep["expert_parallel"] == dep["router_experts"], s
    return Lfm2MoeConfig(
        vocab_size=s["vocab_size"], seq_len=s["max_position_embeddings"],
        d_model=s["hidden_size"], n_layers=s["num_hidden_layers"],
        layer_types=tuple(s["layer_types"][:s["num_hidden_layers"]]),
        n_dense_layers=s["num_dense_layers"],
        n_heads=s["num_attention_heads"], n_kv_heads=s["num_key_value_heads"],
        head_dim=s["hidden_size"] // s["num_attention_heads"],
        conv_taps=s["conv_L_cache"], d_ff=s["intermediate_size"],
        d_expert=s["moe_intermediate_size"],
        n_routed_experts=dep["router_experts"], experts_held=held,
        expert_offset=dep["expert_offset"], expert_parallel=dep["expert_parallel"],
        experts_per_tok=s["num_experts_per_tok"],
        routed_scaling=float(s["routed_scaling_factor"]), route_eps=s["route_eps"],
        norm_eps=s["norm_eps"], rope_theta=float(s["rope_parameters"]["rope_theta"]),
        init_range=s["init_range"], score_spread=s["attention_init"]["score_spread"],
        attn_out_gain=s["attention_init"]["out_gain"],
        expert_out_gain=s["expert_init"]["out_gain"],
        expert_own_share=s["expert_init"]["own_share"], tap_range=s["conv_init"]["tap_range"],
        dtype=s["dtype"],
    )


def program_init():
    from ray_tpu.models.lfm2 import lfm2_moe_init

    return lfm2_moe_init


#: what the reference takes of the program's configuration, by its field names
_REFERENCE_FIELDS = (
    "n_heads", "n_kv_heads", "head_dim", "norm_eps", "rope_theta", "experts_per_tok",
    "expert_offset", "routed_scaling", "route_eps")


def reference_sizes(cfg) -> dict:
    return {k: getattr(cfg, k) for k in _REFERENCE_FIELDS}


#: rows of the expert-layer probe: a prefill chunk's (the tile loop) and a
#: decode batch's (the batch form; on a TPU the Pallas kernel): the engine's
PROBE_CHUNK, PROBE_BATCH = 512, 16
#: the largest relative rms a layer's program may stand from the reference's
#: loop on the same input: the geometric mean of 0.00260 (the configured
#: programs, the largest of 8 layers x 2 forms on the chip) and 0.02427 (the
#: experts' matrices ALONE at 3 bits of mantissa, the SMALLEST): 3.1 times of
#: room on both sides; the configuration's ``correctness`` has every reading
EXPERT_LAYER_TOLERANCE = 0.008
#: a probe row whose routing lies this close to a flip is left out
_PROBE_MARGIN = 1e-4


def probe_rows(first: int, last: int):
    """(the positions the probe reads, how many of them are the chunk's): the
    prompt's last chunk, which ends at ``first`` (the first compared row: the
    prompt's last token), and a decode batch's worth ending at ``last``."""
    import numpy as np

    chunk = np.arange(max(first + 1 - PROBE_CHUNK, 0), first + 1)
    return np.concatenate([chunk, np.arange(max(last + 1 - PROBE_BATCH, 0), last + 1)]), len(chunk)


@functools.lru_cache(maxsize=None)
def _expert_layer_forms(cfg) -> dict:
    """``cfg``'s expert layer as the served steps run it (``_expert_mlp``: the
    norm, ``ops.moe.route``, ``held_pairs``, ``expert_layer``), jitted once a
    configuration: what it ADDS to a stream ``h``, counted as a chunk's rows
    and as a decode's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.lfm2 import COUNTERS

    body = cfg.serving_body()
    counts = jnp.zeros((len(COUNTERS) + cfg.experts_held,), jnp.int32)

    def form(phase):
        return jax.jit(lambda h, layer, experts, index: body._expert_mlp(
            h, layer, jnp.ones((h.shape[0],), bool), counts, phase, experts, index)[0] - h)

    return {"chunk": form("chunk"), "decode": form("decode")}


def expert_layer_deviation(cfg, params, taps: dict, chunk: int) -> list:
    """A dict an expert layer: the relative rms between what ``cfg``'s expert
    layer over ``params`` adds to the stream that entered the REFERENCE's
    layer and what the reference's loop added, as a ``chunk`` (the first
    ``chunk`` tapped rows: 65 rows or more go through the tile loop) and as a
    ``decode`` batch (the others: the batch form)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    forms = _expert_layer_forms(cfg)
    layers = [jax.tree_util.tree_map(lambda a, i=i: a[i], {k: run[k] for k in ("ln2", "router")})
              for run in params["runs"] if "router" in run
              for i in range(run["ln2"]["scale"].shape[0])]
    out = []
    for index, (layer, (h, want, margin)) in enumerate(zip(layers, taps["layers"])):
        line = {"layer": index}
        for phase, rows in (("chunk", slice(0, chunk)), ("decode", slice(chunk, None))):
            got = np.asarray(forms[phase](h[rows], layer, params["experts"], jnp.int32(index)))
            keep = np.asarray(margin[rows]) >= _PROBE_MARGIN
            ref = np.asarray(want[rows])
            line[phase] = float(np.linalg.norm((got - ref)[keep])
                                / max(np.linalg.norm(ref[keep]), 1e-30))
            line[f"{phase}_rows"] = int(keep.sum())
        out.append(line)
    return out


def reference_logits(params, tokens, rows, cfg):
    import numpy as np

    from benchmark import harness as H
    from benchmark.reference import lfm2_moe as reference

    # every row is compared (the configuration's correctness.routing_margin
    # says why a flipped choice gets no margin here): say so, run by run
    margin = reference.ROUTING_MARGIN
    at, chunk = probe_rows(min(rows), max(rows))
    taps = {"rows": at}
    logits = np.asarray(reference.logits_at(
        params, tokens, rows, reference_sizes(cfg), margin, taps))
    # the expert layers of every probe sequence, each against the reference's
    # loop on the reference's own stream: a prompt of 512 tokens or more gives
    # the tile loop a whole chunk
    layers = expert_layer_deviation(cfg, params, taps, chunk)
    worst = max(max(x["chunk"], x["decode"]) for x in layers)
    H.emit("expert_layer_probe", worst=worst, tolerance=EXPERT_LAYER_TOLERANCE, layers=layers)
    H.check(worst <= EXPERT_LAYER_TOLERANCE,
            f"the program's expert layer stands {worst:.4f} (relative rms) from the "
            f"reference's loop on the same input; the limit is {EXPERT_LAYER_TOLERANCE}")
    # a row the reference leaves undetermined is all zero: say how many the
    # harness's comparison is decided by
    H.emit("reference_rows", rows=len(rows), routing_margin=margin,
           undetermined=int((logits == 0).all(axis=-1).sum()))
    return logits


def _n_of(model: dict, kind: str) -> int:
    return list(model["layer_types"]).count(kind)


def _expert_layers(model: dict) -> int:
    return model["n_layers"] - model["n_dense_layers"]


def moe_decode_bytes(touched: float, model: dict) -> float:
    """``touched``: held experts with at least one row, summed over the
    expert layers of ONE decode.  bfloat16 weights: an expert layer's router
    (0.26 MB, its float32 selection bias beside it) always, 18.87 MB a touched
    expert."""
    d = model["d_model"]
    router = d * model["n_routed_experts"] * 2 + model["n_routed_experts"] * 4
    return _expert_layers(model) * router + touched * 3 * d * model["d_expert"] * 2


def moe_chunk_bytes(touched: float, model: dict) -> float:
    """The same for ONE prefill chunk: a touched expert's weights once,
    however many tiles of pairs go through them."""
    return moe_decode_bytes(touched, model)


def moe_pair_flops(model: dict) -> float:
    """The products of one (row, expert) pair: gate, up and down, 2 x d x f
    each."""
    return 6.0 * model["d_model"] * model["d_expert"]


def gqa_decode_kv_bytes(live_tokens: float, model: dict) -> float:
    """Bytes of K and V one decode step must read over the ATTENTION layers:
    every live token's key and value of every key-value head, once, in the
    pool's dtype (2 bytes): 2,048 B a token a layer at 8 heads of 64."""
    return (live_tokens * _n_of(model, "full_attention") * 2 * model["n_kv_heads"]
            * model["head_dim"] * 2.0)


def short_conv_decode_bytes(live_rows: float, model: dict) -> float:
    """Bytes one decode step must move in the CONV layers' mixers: ``W_in``
    (d x 3d), the taps and ``W_out`` (d x d) once a layer in bfloat16, and each
    live row's tails (``conv_taps - 1`` x d, 2 bytes) read once and written
    once."""
    d, taps = model["d_model"], model["conv_taps"]
    weights = (4 * d * d + taps * d) * 2.0
    return _n_of(model, "conv") * (weights + live_rows * (taps - 1) * d * 2 * 2.0)
