"""The cell ``graniteh_draft_sat`` (ISSUE 59): its CPU rehearsal end to end,
its three new readers and the state-space and attention readers it shares
with Falcon-H1's cell on a made-up trace with THIS family's counts, the
family's counts against the issue's arithmetic, the file's sizes against the
catalog row, and the rows the reference would leave open under a routing
margin."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness as H

CELL, CONFIG = "graniteh_draft_sat", "granite-4.0-h-small-ep2-l10-1chip"
#: (HLO text, op_name) of the ops a decode runs, 1 ms each, as a chip trace
#: names them: the SSD kernel lies INSIDE ``ssm``, the tile loop under
#: ``moe_experts``, every layer in one of three ``while`` loops
CALL = "custom-call(...), custom_call_target=\"tpu_custom_call\""
KERNEL = f"%ssd_decode = f32[153,128,64,128] {CALL}"
ATTN = f"%paged_attention_verify = bf16[16,32,128] {CALL}"
OPS = [(KERNEL, "jit(_decode_impl)/while/body/ssm/ssd_update/pallas_call"),
       ("%fusion.2 = f32[16,16768] fusion(...)", "jit(_decode_impl)/while/body/ssm/dot"),
       (ATTN, "jit(_decode_impl)/while/body/gqa_attention/paged_attention/pallas_call"),
       ("%fusion.3 = f32[16,72] fusion(...)", "jit(_decode_impl)/while/body/moe_router/dot"),
       ("%fusion.4 = f32[16,4096] fusion(...)",
        "jit(_decode_impl)/while/body/moe_experts/while/body/dot"),
       ("%fusion.5 = f32[16,4096] fusion(...)",
        "jit(_decode_impl)/while/body/moe_experts/while/body/scatter-add"),
       ("%fusion.6 = f32[16,1536] fusion(...)", "jit(_decode_impl)/while/body/moe_shared/dot")]
#: what ``stats()`` gives at the slice's two ends: the decodes' occupancy
#: under BOTH pools, the expert layer's counts from the device
POOL = ({"decodes": 1000, "decode_rows": 16000, "decode_tokens": 1000 * 16000},
        {"decodes": 1150, "decode_rows": 18400, "decode_tokens": 1150 * 16000})
MOE = ({"decodes": 1003, "decode_pairs": 800000, "decode_touched": 327000,
        "decode_tile_rows": 327000 * 16},
       {"decodes": 1153, "decode_pairs": 920000, "decode_touched": 376050,
        "decode_tile_rows": 376050 * 16})
START, STOP = ({"state_pool": p, "kv_pool": p, "moe": m} for p, m in zip(POOL, MOE))
EXPERT, ALWAYS = 3 * 4096 * 768 * 2, 4096 * 72 * 2 + 3 * 4096 * 1536 * 2


def _config():
    config = H.load_config(H.manifest(), CONFIG)
    return config, H.family_piece(config, "model_config")(H.sizes(config, False))


def _trace(ops=OPS):
    """Two decodes, every op 1 ms, back to back."""
    modules, timed, names, t = [], [], {}, 0.0
    for _ in range(2):
        start = t
        for hlo, op_name in ops:
            timed.append((hlo, t, 1e6))
            names[hlo] = op_name
            t += 1e6
        modules.append((start, t, "jit__decode_impl"))
    return {"ops": timed, "modules": modules, "op_names": names, "spans": []}


def _run(monkeypatch, peaks=True, ends=(START, STOP), trace=None):
    H.load_metric("per_layer", "moe_hybrid_decode_dev_ms")  # layer_metrics/ on the path
    import _inner_scope

    monkeypatch.setattr(_inner_scope, "load", lambda run: {"trace": trace or _trace()})
    config, model = _config()
    counters = dict(zip(("trace_start", "trace_stop", "open", "close"), ends * 2))
    return {"peaks": H.peaks_for("TPU v5 lite") if peaks else None, "config": config,
            "model": dataclasses.asdict(model), "counters": counters, "trace_dir": "x"}


def test_the_readers_of_this_family_on_a_made_up_trace(monkeypatch, capsys):
    run = _run(monkeypatch)
    read = lambda name: H.load_metric("per_layer", name).read(run)  # noqa: E731
    # router 1 + the tile loop's two ops 2 + shared 1 ms a decode
    assert read("moe_hybrid_decode_dev_ms") == pytest.approx(4.0)
    # 10 x (router 0.59 MB + shared 37.75 MB) + 327 touched x 18.87 MB = 6.56 GB
    need = 10 * ALWAYS + 327 * EXPERT
    assert read("moe_hybrid_expert_roofline") == pytest.approx(
        100 * (need / 819e9) / 4.0e-3, rel=1e-6)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert all(x["event"] == "program_spans" and x["touched"] == 327 and x["pairs"] == 800
               and x["tile_rows"] == 327 * 16 and x["between"] == ["trace_start", "trace_stop"]
               for x in lines)
    assert lines[-1]["scope"] == "moe_shared" and lines[-1]["moe_bytes"] == need
    # 327 of the 360 (layer, held expert) slots touched a decode; 800 pairs in
    # 327 tiles of 16 rows
    assert read("experts_touched_share") == pytest.approx(100 * 327 / 360)
    assert read("moe_tile_fill_share") == pytest.approx(100 * 800 / (327 * 16))
    # the readers PR 49 brought, on this family's counts: the kernel ALONE under
    # ssd_update against 16 rows x 9 Mamba layers' states; ONE layer's K/V
    state = 16 * 9 * 128 * 64 * 128 * 4 * 2
    assert read("ssd_decode_roofline") == pytest.approx(100 * (state / 819e9) / 1e-3, rel=1e-6)
    assert read("gqa_attn_dev_ms") == pytest.approx(1.0)


def test_the_new_readers_find_nothing_where_the_program_counts_no_tiles(monkeypatch):
    names = ("moe_tile_fill_share", "moe_hybrid_decode_dev_ms", "moe_hybrid_expert_roofline")
    read = lambda run: [H.load_metric("per_layer", n).read(run) for n in names]  # noqa: E731
    kimi = tuple({"kv_pool": e["kv_pool"], "moe": {
        k: v for k, v in e["moe"].items() if k != "decode_tile_rows"}} for e in (START, STOP))
    for ends in (({}, {}), kimi, ({"state_pool": POOL[0]}, {"state_pool": POOL[1]}),
                 (START, START)):
        assert read(_run(monkeypatch, ends=ends)) == [None] * 3
    # one of the three expert scopes missing is no reading of the expert layers
    run = _run(monkeypatch, trace=_trace([op for op in OPS if "moe_shared" not in op[1]]))
    assert read(run)[1:] == [None, None]
    # a rehearsal has no chip to compare with; a count needs none
    fill, ms, share = read(_run(monkeypatch, peaks=False))
    assert fill is not None and ms == pytest.approx(4.0) and share is None
    assert H.load_metric("per_layer", names[0]).read({"counters": None}) is None


def test_the_cell_is_listed_where_a_reader_finds_something_to_read():
    """``moe_decode_dev_ms`` and ``moe_expert_roofline`` are NOT among them:
    they pass ``_decode_scope.scope_ms`` the occupancy it also reads from
    ``state_pool``, which a hybrid body has (PERF.md section 7)."""
    listed = {m["name"] for m in H.manifest()["per_layer"] if CELL in m.get("workloads", [])}
    assert {"ssm_decode_dev_ms", "ssd_decode_roofline", "gqa_attn_dev_ms",
            "experts_touched_share", "moe_tile_fill_share", "moe_hybrid_decode_dev_ms",
            "moe_hybrid_expert_roofline", "decode_step_dev_ms", "sampler_dev_ms",
            "batch_occupancy", "peak_hbm_gb", "device_idle_share"} <= listed
    assert not {"moe_decode_dev_ms", "moe_expert_roofline", "prefill_chunk_dev_ms",
                "ssd_chunk_dev_ms", "chunk_attn_dev_ms", "prefix_hit_share",
                "gqa_attn_roofline"} & listed
    assert CELL in next(m for m in H.manifest()["end_to_end"]
                        if m["name"] == "itl_p95_ms")["workloads"]


def test_the_familys_counts_are_the_issues_arithmetic():
    config, cfg = _config()
    model = dataclasses.asdict(cfg)
    piece = lambda name: H.family_piece(config, name)  # noqa: E731
    assert EXPERT == 18_874_368 and ALWAYS == 589_824 + 37_748_736
    # a 16-row decode: 32.7 of 36 touched in each of 10 layers, 6.17 GB of routed experts
    assert 36 * (1 - (62 / 72) ** 16) == pytest.approx(32.7, abs=0.05)
    assert piece("moe_decode_bytes")(327, model) == 10 * ALWAYS + 327 * EXPERT
    assert 327 * EXPERT == pytest.approx(6.17e9, rel=2e-3)
    # 9 Mamba layers x 128 x 64 x 128 float32, in and out: 1.21 GB at 16 rows
    assert piece("ssd_decode_state_bytes")(16, model) == 16 * 9 * 4_194_304 * 2
    assert piece("ssd_decode_state_bytes")(16, model) == pytest.approx(1.21e9, rel=2e-3)
    # ONE attention layer: 4,096 B a token
    assert piece("gqa_decode_kv_bytes")(16 * 1000, model) == 16 * 1000 * 4096
    # two periods: the counts follow the layer kinds, not the depth
    two = dict(model, n_layers=20, layer_types=model["layer_types"] * 2)
    assert piece("ssd_decode_state_bytes")(1, two) == 18 * 4_194_304 * 2
    assert piece("gqa_decode_kv_bytes")(1, two) == 2 * 4096
    assert piece("moe_decode_bytes")(0, two) == 20 * ALWAYS
    # what the readers take of the model by name
    assert (model["n_layers"], model["n_dense_layers"], model["experts_held"]) == (10, 0, 36)
    assert piece("SERVE_MODEL") == "granite_h"


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's ``config`` under the same key, but the
    three under ``reduced``, each beside its published value; the published
    ``layer_types`` whole."""
    config, model = _config()
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
        "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 768,
        "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_key_value_heads": 8, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    }
    assert {k: config[k] for k in published} == published
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert config["layer_types"][:10] == period and len(config["layer_types"]) == 40
    assert config["layer_types"].count("attention") == 4
    assert {k: (config[k], v["published"]) for k, v in config["reduced"].items()} == {
        "num_hidden_layers": (10, 40), "num_local_experts": (36, 72),
        "vocab_size": (50176, 100352)}
    entry = next(c for c in H.manifest()["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    dep = config["deployment"]
    assert (dep["router_experts"], dep["expert_parallel"], dep["expert_offset"],
            dep["pipeline_stages"]) == (72, 2, 0, 4)
    assert (model.n_routed_experts, model.experts_held, model.vocab_size) == (72, 36, 50176)
    assert model.layer_types == tuple(period)
    eng = config["engine"]
    assert eng == {"max_slots": 16, "prefill_chunk": 512, "block_size": 128,
                   "max_blocks_per_seq": 14, "num_blocks": 225, "spec_k": 0,
                   "prefix_cache": False}
    assert eng["prefill_chunk"] == 2 * config["mamba_chunk_size"]
    traffic = H.load_traffic("draft_c48")
    assert traffic["max_context"] == eng["max_blocks_per_seq"] * eng["block_size"]
    assert (traffic["system_prompt_len"] + traffic["user_len"][1] + traffic["max_tokens"][1]
            <= traffic["max_context"])
    assert traffic["system_prompt_len"] + traffic["user_len"][1] <= eng["prefill_chunk"]
    lens = config["correctness"]["probe_prompt_lens"]
    assert any(256 < n <= 512 for n in lens) and any(n > 512 for n in lens)
    assert max(lens) + config["correctness"]["probe_out_tokens"] <= traffic["max_context"]


def test_the_references_gap_and_weight_and_the_rows_a_margin_would_leave_open():
    """By hand, from the logits: ``gap`` is the 10th logit less the 11th
    where one of the two experts is held (infinite where neither is),
    ``weight`` the 10th's softmax weight among the chosen; ``logits_at`` says
    nothing on exactly the rows within a margin that carry more than a
    weight, and on none at the configuration's margin of 0."""
    import jax

    from benchmark.reference import granite_h as reference
    from ray_tpu.models.granite_h import GraniteHConfig, granite_h_init

    cfg = GraniteHConfig(
        vocab_size=192, d_model=64, n_layers=4,
        layer_types=("mamba", "mamba", "attention", "mamba"), n_heads=8, n_kv_heads=2,
        head_dim=8, d_ssm=64, ssm_heads=4, d_state=16, ssm_chunk=4, d_expert=16, d_shared=32,
        n_routed_experts=12, experts_held=4, expert_offset=4, experts_per_tok=3,
        embedding_multiplier=4.0, logits_scaling=2.0, attention_multiplier=0.125, init_range=0.25,
        dtype="float32", attn_impl="xla")
    params = granite_h_init(jax.random.PRNGKey(0), cfg)
    consts = H.family_piece(_config()[0], "reference_sizes")(cfg)
    seq = [int(t) for t in np.random.default_rng(3).integers(1, 192, 40)]
    rows = list(range(8, 40))
    h, masks, gaps, weights = reference.forward(params, seq, consts)
    # layer 0 by hand: its input is the embedding through the first mixer
    frozen = reference._frozen(consts)
    layer = jax.tree_util.tree_map(lambda a: a[0], params["runs"][0])
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][np.asarray(seq)] * cfg.embedding_multiplier
        x = reference._mamba(x, layer, frozen)
        z = np.asarray(reference._rmsnorm(x, layer["ln2"]["scale"], cfg.rms_norm_eps)
                       @ layer["router"]["kernel"])
    order = np.argsort(-z, axis=-1)
    for t in range(len(seq)):
        last_in, first_out = order[t, 2], order[t, 3]
        ours = 4 <= last_in < 8 or 4 <= first_out < 8
        want = z[t, last_in] - z[t, first_out] if ours else np.inf
        assert float(gaps[0][t]) == pytest.approx(want, abs=1e-5)
        top = np.exp(z[t, order[t, :3]] - z[t, order[t, 0]])
        assert float(weights[0][t]) == pytest.approx(top[2] / top.sum(), abs=1e-5)
        assert (np.asarray(masks[0][t]) == np.isin(np.arange(4, 8), order[t, :3])).all()
    whole = np.asarray(reference.logits_at(params, seq, rows, consts))
    assert not (whole == 0).all(axis=-1).any()  # margin 0: every row is compared
    least = np.stack([np.asarray(g) for g in gaps])[:, rows]
    carried = np.stack([np.asarray(w) for w in weights])[:, rows]
    margin = float(np.quantile(least[np.isfinite(least)], 0.3))
    for min_weight in (0.0, float(np.median(carried))):
        open_ = ((least < margin) & (carried > min_weight)).any(axis=0)
        got = np.asarray(reference.logits_at(params, seq, rows, consts, margin, min_weight))
        assert 0 < open_.sum() < len(rows)
        assert (got[open_] == 0).all() and (got[~open_] == whole[~open_]).all()


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
        # the device's own counts are read: touched experts, and pairs a tile row
        assert 0 < last["metrics"]["experts_touched_share"]["value"] <= 100
        assert 0 < last["metrics"]["moe_tile_fill_share"]["value"] <= 100
    else:
        assert {"itl_p95_ms", "setup_s"} <= set(last["metrics"])
