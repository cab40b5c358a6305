"""The cell ``lfm2_docqa_sat`` (ISSUE 61): its CPU rehearsal end to end, its
seven new readers and the attention readers it shares with Falcon-H1's cell on
a made-up trace with THIS family's counts, the family's counts against the
issue's arithmetic, the file's sizes against the catalog row, the traffic
against the mix it extends, and the controls' script at the tiny sizes."""

import copy
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness as H

CELL, CONFIG, TRAFFIC = "lfm2_docqa_sat", "lfm2-24b-a2b-l10-1chip", "docqa_c32_s24"
CALL = "custom-call(...), custom_call_target=\"tpu_custom_call\""
ATTN = f"%paged_attention_verify = bf16[16,32,128] {CALL}"
EXPERTS = f"%moe_batch_experts = f32[16,2048] {CALL}"
#: (HLO text, op_name) of the ops a decode and a prefill chunk run, 1 ms each,
#: as a chip trace names them: the convolution's update lies INSIDE
#: ``short_conv``, the batch kernel and the tile loop under ``moe_experts``
DECODE = [("%fusion.1 = f32[16,6144] fusion(...)", "jit(_decode_impl)/while/body/short_conv/dot"),
          ("%fusion.2 = f32[16,2048] fusion(...)",
           "jit(_decode_impl)/while/body/short_conv/conv_update/add"),
          (ATTN, "jit(_decode_impl)/while/body/gqa_attention/paged_attention/pallas_call"),
          ("%fusion.3 = f32[16,64] fusion(...)", "jit(_decode_impl)/while/body/moe_router/dot"),
          (EXPERTS, "jit(_decode_impl)/while/body/moe_experts/pallas_call"),
          ("%fusion.4 = f32[16,11776] fusion(...)", "jit(_decode_impl)/while/body/dense_mlp/dot")]
PREFILL = [("%fusion.5 = f32[512,6144] fusion(...)",
            "jit(_prefill_impl)/while/body/short_conv/dot"),
           ("%fusion.6 = f32[8,2048,512] fusion(...)",
            "jit(_prefill_impl)/while/body/chunk_attention/while/body/dot"),
           ("%fusion.7 = f32[512,64] fusion(...)", "jit(_prefill_impl)/while/body/moe_router/dot"),
           ("%fusion.8 = f32[64,2048] fusion(...)",
            "jit(_prefill_impl)/while/body/moe_experts/while/body/dot"),
           ("%fusion.9 = f32[512,2048] fusion(...)",
            "jit(_prefill_impl)/while/body/moe_experts/while/body/scatter-add")]
#: what ``stats()`` gives at the slice's two ends: 150 decodes of 16 rows and
#: 120 full chunks between them, and the device's own counts of both
POOL = ({"decodes": 1000, "decode_rows": 16000, "decode_tokens": 1000 * 112000,
         "chunks": 800, "chunk_tokens": 800 * 512, "chunk_context_tokens": 800 * 4000},
        {"decodes": 1150, "decode_rows": 18400, "decode_tokens": 1150 * 112000,
         "chunks": 920, "chunk_tokens": 920 * 512, "chunk_context_tokens": 920 * 4000})
MOE = ({"decodes": 1000, "decode_pairs": 512000, "decode_touched": 328000,
        "decode_tile_rows": 328000 * 16, "decode_expert_steps": 328000,
        "chunks": 800, "chunk_pairs": 800 * 16384, "chunk_touched": 800 * 512,
        "chunk_tile_rows": 800 * 32768},
       {"decodes": 1150, "decode_pairs": 588800, "decode_touched": 377200,
        "decode_tile_rows": 377200 * 16, "decode_expert_steps": 377200,
        "chunks": 920, "chunk_pairs": 920 * 16384, "chunk_touched": 920 * 512,
        "chunk_tile_rows": 920 * 32768})
START, STOP = ({"state_pool": p, "kv_pool": p, "moe": m} for p, m in zip(POOL, MOE))
EXPERT, ROUTER = 3 * 2048 * 1536 * 2, 2048 * 64 * 2 + 64 * 4
NEW = ("short_conv_dev_ms", "short_conv_roofline", "moe_routed_decode_dev_ms",
       "moe_routed_expert_roofline", "moe_chunk_dev_ms", "moe_chunk_expert_roofline",
       "moe_chunk_tile_fill_share")


def _config():
    config = H.load_config(H.manifest(), CONFIG)
    return config, H.family_piece(config, "model_config")(H.sizes(config, False))


def _trace(decode=DECODE, prefill=PREFILL):
    """Two decodes and one prefill chunk between them, every op 1 ms."""
    modules, timed, names, t = [], [], {}, 0.0
    for program, ops in (("jit__decode_impl", decode), ("jit__prefill_impl", prefill),
                         ("jit__decode_impl", decode)):
        start = t
        for hlo, op_name in ops:
            timed.append((hlo, t, 1e6))
            names[hlo] = op_name
            t += 1e6
        modules.append((start, t, program))
    return {"ops": timed, "modules": modules, "op_names": names, "spans": []}


def _run(monkeypatch, peaks=True, ends=(START, STOP), trace=None):
    H.load_metric("per_layer", "moe_routed_decode_dev_ms")  # layer_metrics/ on the path
    import _inner_scope

    monkeypatch.setattr(_inner_scope, "load", lambda run: {"trace": trace or _trace()})
    config, model = _config()
    counters = dict(zip(("trace_start", "trace_stop", "open", "close"), ends * 2))
    return {"peaks": H.peaks_for("TPU v5 lite") if peaks else None, "config": config,
            "model": dataclasses.asdict(model), "counters": counters, "trace_dir": "x"}


def test_the_readers_of_this_family_on_a_made_up_trace(monkeypatch, capsys):
    run = _run(monkeypatch)
    read = lambda name: H.load_metric("per_layer", name).read(run)  # noqa: E731
    # the mixer's product and the update inside it: 2 ms a decode
    assert read("short_conv_dev_ms") == pytest.approx(2.0)
    conv = 8 * (16_783_360 * 2 + 16 * 2 * 2048 * 2 * 2)
    assert read("short_conv_roofline") == pytest.approx(100 * (conv / 819e9) / 2e-3, rel=1e-6)
    # router 1 + the batch kernel 1 ms a decode; the dense MLP is not the expert layer's
    assert read("moe_routed_decode_dev_ms") == pytest.approx(2.0)
    need = 8 * ROUTER + 328 * EXPERT
    assert read("moe_routed_expert_roofline") == pytest.approx(
        100 * (need / 819e9) / 2e-3, rel=1e-6)
    # a chunk: router 1 + the tile loop's two ops 2 ms; all 512 (layer, expert)
    # slots touched, 16,384 pairs: the BYTES bound (11.8 ms) is over the
    # products' (1.6 ms at the peak)
    assert read("moe_chunk_dev_ms") == pytest.approx(3.0)
    need = 8 * ROUTER + 512 * EXPERT
    flops = 16384 * 6 * 2048 * 1536
    assert need / 819e9 > flops / 197e12
    assert read("moe_chunk_expert_roofline") == pytest.approx(
        100 * (need / 819e9) / 3e-3, rel=1e-6)
    assert read("moe_chunk_tile_fill_share") == pytest.approx(50.0)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert all(x["event"] == "program_spans" for x in lines)
    chunk = [x for x in lines if x["program"] == "prefill"]
    assert all(x["touched"] == 512 and x["pairs"] == 16384 and x["tile_rows"] == 32768
               and x["between"] == ["trace_start", "trace_stop"] for x in chunk)
    assert chunk[0]["chunk_tokens"] == 512 and chunk[0]["chunk_context_tokens"] == 4000
    decode = [x for x in lines if x["scope"] == "moe_experts" and x["program"] != "prefill"]
    assert decode[0]["touched"] == 328 and decode[0]["largest_op"].startswith("moe_batch")
    # the readers the cell shares: 328 of 512 slots touched a decode, every
    # touched expert through the batch form, 3,413 pairs in 328 x 16 rows
    assert read("experts_touched_share") == pytest.approx(100 * 328 / 512)
    assert read("moe_batch_form_share") == pytest.approx(100.0)
    assert read("moe_tile_fill_share") == pytest.approx(100 * 512 / (328 * 16))
    assert read("gqa_attn_dev_ms") == pytest.approx(1.0)
    kv = 112000 * 2 * 2 * 8 * 64 * 2
    assert read("gqa_attn_roofline") == pytest.approx(100 * (kv / 819e9) / 1e-3, rel=1e-6)
    assert read("chunk_attn_dev_ms") == pytest.approx(1.0)
    # a body with a shared expert is the other pair's to read
    assert read("moe_hybrid_decode_dev_ms") is None


def test_the_experts_time_is_of_the_steps_their_counts_are_of(monkeypatch, capsys):
    """The slice's two ``stats()`` readings bracket the LAST two of three
    traced decodes (the first reading returned a step late): the first
    decode, five times as slow, is in neither the counts nor the time."""
    modules, timed, names, t = [], [], {}, 0.0
    for program, ops, each in (("jit__decode_impl", DECODE, 5e6), ("jit__prefill_impl", PREFILL, 1e6),
                               ("jit__decode_impl", DECODE, 1e6), ("jit__decode_impl", DECODE, 1e6)):
        start = t
        for hlo, op_name in ops:
            timed.append((hlo, t, each))
            names[hlo] = op_name
            t += each
        modules.append((start, t, program))
    trace = {"ops": timed, "modules": modules, "op_names": names, "spans": []}
    late = copy.deepcopy(START)
    late["moe"].update(decodes=1148, decode_touched=377200 - 2 * 300, decode_pairs=588800 - 128,
                       decode_tile_rows=(377200 - 2 * 300) * 16)
    run = _run(monkeypatch, ends=(late, STOP), trace=trace)
    need = 8 * ROUTER + 300 * EXPERT
    assert H.load_metric("per_layer", "moe_routed_expert_roofline").read(run) == pytest.approx(
        100 * (need / 819e9) / 2e-3, rel=1e-6)
    line = [json.loads(x) for x in capsys.readouterr().out.splitlines()][-1]
    assert (line["between"], line["counted_steps"], line["ms_per_step"]) == (
        ["trace_start", "trace_stop"], 2, pytest.approx(2.0))


def test_the_new_readers_find_nothing_in_a_program_without_what_they_read(monkeypatch):
    read = lambda run: [H.load_metric("per_layer", n).read(run) for n in NEW]  # noqa: E731
    granite = tuple({"state_pool": e["state_pool"], "moe": {
        k: v for k, v in e["moe"].items() if not k.startswith("chunk_t")}}
        for e in (START, STOP))
    # the parent of this PR on its own hybrid cell: no ``short_conv`` scope, a
    # ``moe_shared`` scope, no count of a chunk's touched experts or tile rows
    shared = DECODE[2:5] + [("%fusion.0 = f32[16,1536] fusion(...)",
                             "jit(_decode_impl)/while/body/moe_shared/dot")]
    run = _run(monkeypatch, ends=granite, trace=_trace(shared, PREFILL[1:]))
    assert read(run) == [None] * 7
    for ends in (({}, {}), (START, START)):
        got = read(_run(monkeypatch, ends=ends))
        assert got[2:] == [None] * 5
    # a rehearsal has no chip to compare with; a time and a count need none
    got = dict(zip(NEW, read(_run(monkeypatch, peaks=False))))
    assert [k for k, v in got.items() if v is None] == [
        "short_conv_roofline", "moe_routed_expert_roofline", "moe_chunk_expert_roofline"]
    assert H.load_metric("per_layer", NEW[-1]).read({"counters": None}) is None


def test_the_cell_is_listed_where_a_reader_finds_something_to_read():
    """``moe_hybrid_*`` are NOT among them (they want a ``moe_shared`` scope),
    nor the state-space readers (the tails are no scan)."""
    man = H.manifest()
    listed = {m["name"] for m in man["per_layer"] if CELL in m.get("workloads", [])}
    assert set(NEW) <= listed
    assert {"out_tokens_per_s", "batch_occupancy", "preemptions_per_100req", "peak_hbm_gb",
            "decode_step_dev_ms", "prefill_chunk_dev_ms", "device_idle_share", "step_host_ms",
            "step_wall_max_ms", "loop_outside_step_share", "submit_lock_wait_ms",
            "queue_wait_ms", "idle_attributed_share", "sampler_dev_ms", "sent_itl_p95_ms",
            "acked_itl_p95_ms", "written_itl_p95_ms", "stream_wake_p95_ms", "head_hold_p95_ms",
            "emit_itl_p95_ms", "setup_replica_init_s", "setup_backend_init_s",
            "setup_weights_s", "setup_warmup_s", "setup_worker_boot_s", "setup_compile_s",
            "experts_touched_share", "moe_batch_form_share", "moe_tile_fill_share",
            "gqa_attn_dev_ms", "gqa_attn_roofline", "chunk_attn_dev_ms"} <= listed
    assert not {"moe_hybrid_decode_dev_ms", "moe_hybrid_expert_roofline", "moe_decode_dev_ms",
                "moe_expert_roofline", "ssm_decode_dev_ms", "ssd_decode_roofline",
                "ssd_chunk_dev_ms", "prefix_hit_share"} & listed
    # the seven are this cell's alone, and the cell's metrics move what it reports
    for m in man["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
    assert CELL in next(m for m in man["end_to_end"] if m["name"] == "itl_p95_ms")["workloads"]
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)


def test_the_configuration_keeps_every_published_key_and_the_traffic_its_mix():
    config, model = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    published = dict(row["config"], num_hidden_layers=10)
    assert {k: config[k] for k in published} == published
    assert config["source"] == row["source_url"]
    assert {k: (config[k], v["published"]) for k, v in config["reduced"].items()} == {
        "num_hidden_layers": (10, 40)}
    entry = next(c for c in H.manifest()["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == config["source"]
    assert len(config["layer_types"]) == 40 and model.layer_types == tuple(
        config["layer_types"][:10])
    assert model.runs() == (("conv", "dense", 2), ("full_attention", "moe", 1),
                            ("conv", "moe", 3), ("full_attention", "moe", 1), ("conv", "moe", 3))
    dep = config["deployment"]
    assert (dep["router_experts"], dep["expert_parallel"], dep["expert_offset"],
            dep["pipeline_stages"]) == (64, 1, 0, 4)
    eng = config["engine"]
    assert eng == {"max_slots": 16, "prefill_chunk": 512, "block_size": 128,
                   "max_blocks_per_seq": 100, "num_blocks": 1601, "spec_k": 0,
                   "prefix_cache": False}
    # the mix as it stands, 24 sessions a caller and nothing else changed
    traffic, base = H.load_traffic(TRAFFIC), H.load_traffic("docqa_c32")
    changed = {k for k in set(traffic) | set(base) if traffic.get(k) != base.get(k)}
    assert changed == {"name", "sessions_per_client", "why_24"}
    assert (traffic["sessions_per_client"], base["sessions_per_client"]) == (24, 12)
    assert traffic["max_context"] == eng["max_blocks_per_seq"] * eng["block_size"]
    # probes that cross a chunk and a block boundary and reach 6,000 tokens
    lens = config["correctness"]["probe_prompt_lens"]
    assert max(lens) >= 6000 and any(n % 512 and n > 512 for n in lens)
    assert max(lens) + config["correctness"]["probe_out_tokens"] <= traffic["max_context"]
    # a decode's and a chunk's expert reads by the issue's arithmetic
    piece = lambda name: H.family_piece(config, name)  # noqa: E731
    asdict = dataclasses.asdict(model)
    assert 64 * (1 - (60 / 64) ** 16) == pytest.approx(41.2, abs=0.05)
    assert piece("moe_decode_bytes")(8 * 41.2, asdict) == pytest.approx(6.2e9, rel=0.01)
    assert piece("moe_chunk_bytes")(512, asdict) == pytest.approx(9.7e9, rel=0.01)
    assert 2048 * piece("moe_pair_flops")(asdict) * 8 == pytest.approx(0.31e12, rel=0.01)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cells_rehearsal_reads_correct_and_every_listed_counter(trace):
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
    if not trace:
        assert {"itl_p95_ms", "setup_s"} <= set(last["metrics"])
        return
    # every listed metric that needs no device trace is read on a CPU
    man = H.manifest()
    wanted = {m["name"] for m in man["per_layer"] if CELL in m.get("workloads", [])
              and m["source"] != "device_trace"} - {"peak_hbm_gb"}
    assert wanted <= set(last["metrics"]), wanted - set(last["metrics"])
    for name in ("experts_touched_share", "moe_batch_form_share", "moe_tile_fill_share",
                 "moe_chunk_tile_fill_share"):
        assert 0 < last["metrics"][name]["value"] <= 100


def test_the_controls_script_runs_at_the_tiny_sizes(tmp_path):
    out = tmp_path / "controls.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(H.BENCH_DIR, "tests", "lfm2_controls.py"), "--rehearsal",
         "--out", str(out)], cwd=H.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    report = json.loads(out.read_text())
    got = report["controls"]
    assert set(got) == {"configured", "configured_seed11", "configured_seed20260517",
                        "configured_seed3000000019", "mantissa3_tails", "mantissa3_kv",
                        "experts_shifted", "bf16_router", "witness", "mantissa3_experts",
                        "mantissa3_weights"}
    # float32 programs: sound readings pass, the four planted controls fail
    assert all(got[k]["ok"] for k in got if k.startswith("configured"))
    assert not any(got[k]["ok"] for k in ("mantissa3_tails", "mantissa3_kv", "experts_shifted",
                                          "mantissa3_weights"))
    assert report["routing_margin"] == 0.0 and got["configured"]["rows"] == 24
    # the expert layer against the reference's loop, layer by layer: the
    # programs pass, the planted faults of the expert layer fail in EVERY layer
    probe = report["expert_layer_probe"]
    assert probe["configured"]["ok"] and probe["configured"]["largest"] < 1e-5
    for name in ("experts_shifted", "mantissa3_experts", "mantissa3_weights"):
        assert probe[name]["smallest"] > probe[name]["tolerance"] == 0.008


def test_the_floor_script_runs_at_the_tiny_sizes(tmp_path):
    """Float32 programs with bfloat16 rounding in one class of layers: none
    reads nothing, the leading layers' convolutions most of all of it."""
    out = tmp_path / "floor.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(H.BENCH_DIR, "tests", "lfm2_floor.py"), "--rehearsal",
         "--only", "none,all,conv_first,experts", "--out", str(out)], cwd=H.ROOT,
        capture_output=True, text=True, timeout=900, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    rms = json.loads(out.read_text())["rms"]
    assert rms["none"] < 1e-4 < rms["experts"] < rms["conv_first"] <= 1.2 * rms["all"]
