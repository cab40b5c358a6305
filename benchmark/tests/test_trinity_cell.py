"""The cell ``trinity_mixedlen_sat`` (ISSUE 69): its CPU rehearsal end to end,
the two readers it brings and the ones it shares on a made-up trace with THIS
family's counts, the family's counts against the issue's arithmetic, and the
file's sizes against the catalog row."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness as H

CELL, CONFIG = "trinity_mixedlen_sat", "trinity-large-ep8-l5-1chip"
CALL = "custom-call(...), custom_call_target=\"tpu_custom_call\""
#: (HLO text, op_name) of the ops a decode runs, 1 ms each, as a chip trace names them
DECODE = [
    (f"%paged_attention_verify.1 = bf16[16,6,8,128] {CALL}",
     "jit(_decode_impl)/while/body/window_attention/paged_attention/pallas_call"),
    (f"%paged_attention_verify.2 = bf16[16,6,8,128] {CALL}",
     "jit(_decode_impl)/while/body/gqa_attention/paged_attention/pallas_call"),
    ("%fusion.3 = f32[16,256] fusion(...)", "jit(_decode_impl)/while/body/moe_router/dot"),
    (f"%moe_batch_experts = f32[16,3072] {CALL}",
     "jit(_decode_impl)/while/body/moe_experts/pallas_call"),
    ("%fusion.6 = f32[16,3072] fusion(...)", "jit(_decode_impl)/while/body/moe_shared/dot"),
]
#: what ``stats()`` gives at the slice's two ends: 150 decodes of 16 rows at
#: 17k tokens of context, of which a window layer sees 4,096 a row
POOL = ({"decodes": 1000, "decode_rows": 16000, "decode_tokens": 1000 * 272000,
         "decode_window_tokens": 1000 * 65536, "chunks": 200, "chunk_tokens": 200 * 500,
         "chunk_context_tokens": 200 * 9000, "window_blocks_held": 500, "full_blocks_held": 2000},
        {"decodes": 1150, "decode_rows": 18400, "decode_tokens": 1150 * 272000,
         "decode_window_tokens": 1150 * 65536, "chunks": 240, "chunk_tokens": 240 * 500,
         "chunk_context_tokens": 240 * 9000, "window_blocks_held": 560, "full_blocks_held": 2240})
MOE = ({"decodes": 1000, "decode_pairs": 32000, "decode_touched": 28000,
        "decode_tile_rows": 28000 * 16, "decode_expert_steps": 28000},
       {"decodes": 1150, "decode_pairs": 36800, "decode_touched": 32200,
        "decode_tile_rows": 32200 * 16, "decode_expert_steps": 32200})
START, STOP = ({"state_pool": p, "kv_pool": p, "moe": m} for p, m in zip(POOL, MOE))
EXPERT = 3 * 3072 * 3072 * 2
ROUTER = 3072 * 256 * 2 + 256 * 4


def _config():
    config = H.load_config(H.manifest(), CONFIG)
    return config, H.family_piece(config, "model_config")(H.sizes(config, False))


def _trace():
    """Two decodes, every op 1 ms, back to back."""
    modules, timed, names, t = [], [], {}, 0.0
    for _ in range(2):
        start = t
        for hlo, op_name in DECODE:
            timed.append((hlo, t, 1e6))
            names[hlo] = op_name
            t += 1e6
        modules.append((start, t, "jit__decode_impl"))
    return {"ops": timed, "modules": modules, "op_names": names, "spans": []}


def _run(monkeypatch, ends=(START, STOP)):
    H.load_metric("per_layer", "window_attn_roofline")  # layer_metrics/ on the path
    import _decode_scope
    import _inner_scope

    trace = _trace()
    by_scope = {"window_attention": {"paged_attention_verify.1": 2e-3},
                "gqa_attention": {"paged_attention_verify.2": 2e-3}}
    monkeypatch.setattr(_inner_scope, "load", lambda run: {"trace": trace})
    monkeypatch.setattr(_decode_scope, "load", lambda run: {
        "trace": trace, "decodes": 2, "decode_by_scope": by_scope})
    config, model = _config()
    counters = dict(zip(("trace_start", "trace_stop", "open", "close"), ends * 2))
    return {"peaks": H.peaks_for("TPU v5 lite"), "config": config,
            "model": dataclasses.asdict(model), "counters": counters, "trace_dir": "x"}


def test_the_readers_on_a_made_up_trace(monkeypatch, capsys):
    run = _run(monkeypatch)
    read = lambda name: H.load_metric("per_layer", name).read(run)  # noqa: E731
    hbm = run["peaks"]["hbm_bytes_per_s"]
    # a decode: 1 ms under each attention scope; a window layer's rows see
    # 16 x 4,096 tokens, the full layer's 272,000
    assert read("window_attn_dev_ms") == pytest.approx(1.0)
    need = 65536 * 4 * 4096
    assert read("window_attn_roofline") == pytest.approx(100 * (need / hbm) / 1e-3, rel=1e-6)
    line = [json.loads(x) for x in capsys.readouterr().out.splitlines()][-1]
    assert line["window_tokens"] == 65536 and line["kv_bytes"] == need
    assert line["live_tokens"] == 272000 and line["scope"] == "window_attention"
    assert read("gqa_attn_dev_ms") == pytest.approx(1.0)
    assert read("gqa_attn_roofline") == pytest.approx(
        100 * (272000 * 4096 / hbm) / 1e-3, rel=1e-6)
    # the expert layer: router 1 + the batch kernel 1 + shared 1 ms; 28 touched a decode
    assert read("moe_hybrid_decode_dev_ms") == pytest.approx(3.0)
    assert read("moe_hybrid_expert_roofline") == pytest.approx(
        100 * ((4 * (ROUTER + EXPERT) + 28 * EXPERT) / hbm) / 3e-3, rel=1e-6)
    assert read("experts_touched_share") == pytest.approx(100 * 28 / 128)
    # window blocks over full blocks, summed over the four readings
    assert read("window_blocks_held_share") == pytest.approx((500 + 560) / (2000 + 2240))


def test_the_new_readers_read_nothing_on_a_program_without_the_counters(monkeypatch):
    """The parent of PR 69 (and every other family): no ``decode_window_tokens``,
    no window blocks, nothing raised."""
    old = tuple({k: {c: v for c, v in part.items() if "window" not in c and "full_" not in c}
                 for k, part in end.items()} for end in (START, STOP))
    run = _run(monkeypatch, old)
    assert H.load_metric("per_layer", "window_attn_roofline").read(run) is None
    assert H.load_metric("per_layer", "window_blocks_held_share").read(run) is None
    for empty in ({"counters": None}, {"counters": {}}, {}):
        assert H.load_metric("per_layer", "window_blocks_held_share").read(empty) is None


def test_the_cell_is_listed_where_a_reader_finds_something_to_read():
    listed = {m["name"] for m in H.manifest()["per_layer"] if CELL in m.get("workloads", [])}
    assert {"window_attn_roofline", "window_blocks_held_share", "window_attn_dev_ms",
            "gqa_attn_dev_ms", "gqa_attn_roofline", "chunk_attn_dev_ms", "experts_touched_share",
            "moe_tile_fill_share", "moe_batch_form_share", "moe_chunk_tile_fill_share",
            "moe_hybrid_decode_dev_ms", "moe_hybrid_expert_roofline", "decode_step_dev_ms",
            "prefill_chunk_dev_ms", "sampler_dev_ms", "batch_occupancy", "peak_hbm_gb",
            "preemptions_per_100req", "device_idle_share"} <= listed
    # what refuses a ``moe_shared`` scope, crashes beside a ``state_pool``, or
    # needs a prefix cache
    assert not {"moe_decode_dev_ms", "moe_expert_roofline", "moe_chunk_dev_ms",
                "moe_chunk_expert_roofline", "moe_routed_decode_dev_ms",
                "moe_routed_expert_roofline", "prefix_hit_share"} & listed
    assert CELL in next(m for m in H.manifest()["end_to_end"]
                        if m["name"] == "itl_p95_ms")["workloads"]
    new = [m for m in H.manifest()["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == ["window_attn_roofline", "window_blocks_held_share"]
    assert all(m["moves"] == "itl_p95_ms" for m in new)


def test_the_familys_counts_are_the_issues_arithmetic():
    config, cfg = _config()
    model = dataclasses.asdict(cfg)
    piece = lambda name: H.family_piece(config, name)  # noqa: E731
    # one routed expert 28.31M parameters = 56.6 MB; 4 KB of K/V a token a layer
    assert EXPERT == 56_623_104 and 2 * 8 * 128 * 2 == 4096
    assert piece("gqa_decode_kv_bytes")(1000, model) == 1000 * 4096          # ONE full layer
    assert piece("window_decode_kv_bytes")(1000, model) == 1000 * 4 * 4096   # four window layers
    # 16 rows past the window: 1.07 GB a decode, whatever the context
    assert piece("window_decode_kv_bytes")(16 * 4096, model) == pytest.approx(1.07e9, rel=5e-3)
    # a 16-row decode touches 7 of 32 held experts by independent uniform choices
    assert 32 * (1 - (252 / 256) ** 16) == pytest.approx(7.1, abs=0.1)
    assert piece("moe_decode_bytes")(28, model) == 4 * (ROUTER + EXPERT) + 28 * EXPERT
    assert piece("moe_chunk_bytes")(128, model) == 4 * (ROUTER + EXPERT) + 128 * EXPERT
    assert piece("moe_pair_flops")(model) == 6 * 3072 * 3072
    # what the readers take of the model by name
    assert (model["n_layers"], model["n_dense_layers"], model["experts_held"]) == (5, 1, 32)
    assert piece("SERVE_MODEL") == "afmoe"


def test_the_routing_margin_is_the_configurations():
    from benchmark.reference import afmoe as reference

    config, cfg = _config()
    assert reference.ROUTING_MARGIN == config["correctness"]["routing_margin"]
    assert H.family_piece(config, "routing_margin")(cfg) == reference.ROUTING_MARGIN
    tiny = H.family_piece(config, "model_config")(H.sizes(config, True))
    assert H.family_piece(config, "routing_margin")(tiny) == 0.0  # float32: no such products


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's ``config`` under the same key, but the
    four under ``reduced``, beside their published values; ``layer_types`` whole."""
    config, model = _config()
    every = ["sliding_attention"] * 3 + ["full_attention"]
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 3072, "intermediate_size": 12288, "layer_types": every * 15,
        "load_balance_coeff": 5e-05, "max_position_embeddings": 262144, "model_type": "afmoe",
        "moe_intermediate_size": 3072, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 48, "num_expert_groups": 1, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.448, "score_func": "sigmoid", "sliding_window": 4096,
        "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    }
    assert {k: config[k] for k in published} == published
    assert {k: (config[k], v["published"]) for k, v in config["reduced"].items()} == {
        "num_hidden_layers": (5, 60), "num_dense_layers": (1, 6), "num_experts": (32, 256),
        "vocab_size": (25024, 200192)}
    entry = next(c for c in H.manifest()["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(config["reduced"]) and entry["source"] == config["source"]
    dep = config["deployment"]
    assert (dep["router_experts"], dep["expert_parallel"], dep["expert_offset"],
            dep["first_layer"], dep["chips"]) == (256, 8, 0, 5, 1)
    assert model.layer_types == ("sliding_attention", "sliding_attention", "full_attention",
                                 "sliding_attention", "sliding_attention")
    assert (model.n_routed_experts, model.experts_held, model.vocab_size, model.n_layers,
            model.n_dense_layers, model.window) == (256, 32, 25024, 5, 1, 4096)
    assert model.vocab_size * 8 == 200192 and model.cache_kind == "windowed"
    eng = config["engine"]
    assert eng == {"max_slots": 16, "prefill_chunk": 512, "block_size": 128,
                   "max_blocks_per_seq": 262, "num_blocks": 4193, "spec_k": 0,
                   "prefix_cache": False}
    assert eng["num_blocks"] == eng["max_slots"] * eng["max_blocks_per_seq"] + 1
    from ray_tpu.llm.cache import LayerTypedConfig

    geo = LayerTypedConfig(eng["num_blocks"], eng["block_size"], eng["max_blocks_per_seq"],
                           model.window, eng["prefill_chunk"], eng["max_slots"])
    assert (geo.window_blocks_per_seq, geo.window_num_blocks) == (37, 16 * 37 + 1)
    traffic = H.load_traffic("mixedlen_c32")
    assert traffic["kind"] == "closed_sessions" and traffic["clients"] == 32
    assert traffic["max_context"] == eng["max_blocks_per_seq"] * eng["block_size"] == 33536
    assert (traffic["system_prompt_len"] + traffic["user_len"][1] + traffic["max_tokens"][1]
            == traffic["max_context"])
    assert traffic["system_prompt_len"] + traffic["user_len"][0] == 1024
    assert traffic["max_tokens"] == [256, 768] and traffic["queue_is_load"]
    lens = config["correctness"]["probe_prompt_lens"]
    # under a block, past a chunk, past the window, past two windows
    assert lens == [48, 700, 4500, 9000]
    assert max(lens) + config["correctness"]["probe_out_tokens"] <= traffic["max_context"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cells_rehearsal_reads_correct(trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(H.BENCH_DIR, "run.py"), "--workload", CELL,
         "--seed", "3000000011", "--seconds", "4", "--trace", str(trace), "--rehearsal"],
        cwd=H.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 3, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
    last = lines[-1]
    assert last["event"] == "rehearsal_result" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    ref = next(x for x in lines if x["event"] == "correctness")  # may be a cached verdict
    assert ref["reference_ok"] and ref["pool_audit_ok"] and ref["prefix_audit_ok"]
    assert ref["reference"]["positions"] == 24 and ref["reference"]["max_deficit"] < 1e-3
    if trace:
        # the pool's own counts are read, and the device's
        assert 0 < last["metrics"]["window_blocks_held_share"]["value"] < 1
        assert 0 < last["metrics"]["experts_touched_share"]["value"] <= 100
        assert last["metrics"]["moe_batch_form_share"]["value"] == 100.0
        assert last["metrics"]["preemptions_per_100req"]["value"] == 0.0
    else:
        assert {"itl_p95_ms", "setup_s"} <= set(last["metrics"])
