"""The yardstick's own arithmetic: generators, percentile rule, manifest,
trace reduction, and that the harness is driven by data."""

import gzip
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import harness as H
from benchmark import trace_reduce as tr
from benchmark.client import _Chunked
from benchmark.traffic_kinds import closed_sessions, open_loop, train_fixed_shape

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


# -- traffic -----------------------------------------------------------------


@pytest.mark.parametrize("name,kind", [
    ("chat_r80_l14_v2", open_loop), ("shared_prefix_c120", closed_sessions),
    ("sessions48", closed_sessions),
])
def test_same_seed_same_requests_other_seed_others(name, kind):
    traffic = H.load_traffic(name)
    a = kind.make_plan(dict(traffic), 5, 50400, 40.0)
    b = kind.make_plan(dict(traffic), 5, 50400, 40.0)
    c = kind.make_plan(dict(traffic), 6, 50400, 40.0)
    assert a == b
    assert a != c


def test_open_loop_sends_the_same_set_of_lengths_for_every_seed():
    traffic = H.load_traffic("chat_r80_l14_v2")
    plans = [open_loop.make_plan(dict(traffic), s, 50400, 40.0) for s in (1, 2)]

    def lengths(plan, tag):
        reqs = [r for r in plan["requests"] if r["id"].startswith(tag)]
        return (sorted(len(r["payload"]["prompt"]) for r in reqs),
                sorted(r["payload"]["max_tokens"] for r in reqs))

    assert lengths(plans[0], "w") == lengths(plans[1], "w")
    window = [r for r in plans[0]["requests"] if r["id"].startswith("w")]
    assert len(window) == round(traffic["rate"] * 40.0)
    assert all(traffic["lead_s"] <= r["due"] < traffic["lead_s"] + 40.0 for r in window)
    spec = traffic["prompt_len"]
    assert all(spec["min"] <= len(r["payload"]["prompt"]) <= spec["max"] for r in window)
    sampled = [r for r in window if "temperature" in r["payload"]]
    assert len(sampled) == round(len(window) * traffic["sampled_share"])
    assert len({r["payload"]["seed"] for r in sampled}) == len(sampled)


def test_the_seed_draws_the_schedule_and_the_set_of_lengths_stays():
    """No mix replays one schedule: ``--seed`` moves the due instants, the
    order and who gets which length; the stratified grids keep the SET."""
    traffic = H.load_traffic("chat_r80_l14_v2")
    a, b = (open_loop.make_plan(dict(traffic), s, 50400, 50.0) for s in (1, 2))
    shape = lambda p: [(r["due"], len(r["payload"]["prompt"]), r["payload"]["max_tokens"],  # noqa: E731
                        "temperature" in r["payload"]) for r in p["requests"]]
    assert shape(a) != shape(b)
    assert "schedule_seed" not in traffic
    for name in ("shared_prefix_c120", "sessions48"):
        mix = H.load_traffic(name)
        assert "schedule_seed" not in mix
        e, f = (closed_sessions.make_plan(dict(mix), s, 50400, 50.0) for s in (1, 2))
        for j in range(mix["sessions_per_client"]):  # every round, the whole grid
            first = lambda p: sorted(  # noqa: E731
                (len(c[j]["turns"][0]["user"]), c[j]["turns"][0]["max_tokens"])
                for c in p["clients"])
            if name == "shared_prefix_c120":
                assert sorted(x for x, _ in first(e)) == sorted(x for x, _ in first(f))
                assert sorted(y for _, y in first(e)) == sorted(y for _, y in first(f))
        order = lambda p: [len(c[0]["turns"][0]["user"]) for c in p["clients"]]  # noqa: E731
        assert order(e) != order(f)


def test_the_backlog_mix_primes_its_prefixes_and_keeps_the_queue_full():
    mix = H.load_traffic("shared_prefix_c120")
    plan = closed_sessions.make_plan(dict(mix), 3, 50400, 50.0)
    systems = {tuple(s["system"]) for c in plan["clients"] for s in c}
    assert len(systems) == mix["system_prompts"]
    assert {tuple(p["prompt"]) for p in plan["primers"]} == systems
    assert [p["max_tokens"] for p in plan["primers"]] == [1, 1, 1, 1, mix["gate_tokens"]]
    assert plan["queue_is_load"] and len(plan["clients"]) == 120
    engine = H.load_json(os.path.join(H.BENCH_DIR, "configs", "gptj-6b-l14-1chip.json"))
    assert len(plan["clients"]) >= 3 * engine["engine"]["max_slots"]
    # the harness's own stats(), audit() and trace calls need a free thread
    assert len(plan["clients"]) <= engine["deployment"]["max_ongoing_requests"] - 8
    assert all(len(s["turns"]) == 1 and s["turns"][0]["think_s"] == 0.0
               for c in plan["clients"] for s in c)
    assert not open_loop.make_plan(H.load_traffic("chat_r80_l14_v2"), 1, 50400, 5.0)["queue_is_load"]


def test_sessions_stay_under_the_context_limit():
    traffic = H.load_traffic("sessions48")
    plan = closed_sessions.make_plan(traffic, 3, 50400, 40.0)
    assert len(plan["clients"]) == traffic["clients"]
    for sessions in plan["clients"]:
        for s in sessions:
            total = len(s["system"]) + sum(
                len(t["user"]) + t["max_tokens"] for t in s["turns"])
            assert total <= traffic["max_context"]
            assert len(s["system"]) == traffic["system_prompt_len"]


def test_train_batches_repeat_with_the_seed_and_follow_zipf():
    a = next(train_fixed_shape.batches(3, 4, 64, 1000, 1.0))
    b = next(train_fixed_shape.batches(3, 4, 64, 1000, 1.0))
    c = next(train_fixed_shape.batches(4, 4, 64, 1000, 1.0))
    assert (a == b).all() and (a != c).any()
    assert a.shape == (4, 65) and a.min() >= 0 and a.max() < 1000
    big = next(train_fixed_shape.batches(0, 64, 1023, 1000, 1.0))
    assert (big == 0).mean() > 5 * (big == 9).mean()  # rank 1 ten times rank 10


# -- arithmetic ----------------------------------------------------------------


@pytest.mark.parametrize("values,p,want", [
    (range(1, 101), 90, 90), (range(1, 101), 95, 95), (range(1, 101), 100, 100),
    (range(1, 11), 90, 9), (range(1, 11), 91, 10), ([7], 50, 7), ([3, 1, 2], 50, 2),
    ([3, 1, 2], 1, 1),
])
def test_percentile_is_nearest_rank_and_always_a_measured_value(values, p, want):
    assert H.percentile(list(values), p) == want


def test_percentile_and_median_refuse_an_empty_sample():
    with pytest.raises(ValueError):
        H.percentile([], 90)
    with pytest.raises(ValueError):
        H.median([])
    assert H.median([1, 2, 3, 10]) == 2.5


def test_chunked_decoder_gives_token_lines_whatever_the_packet_boundaries():
    body = b"".join(b"%x\r\n%s\r\n" % (len(x), x) for x in (b"12\n", b"345\n6", b"7\n"))
    body += b"0\r\n\r\n"
    for step in (1, 2, 5, 1000):
        dec, out = _Chunked(), []
        for i in range(0, len(body), step):
            out += dec.feed(body[i:i + step])
        assert out == [b"12", b"345", b"67"] and dec.done


# -- what counts as failed -------------------------------------------------------


def _rec(**kw):
    base = {"id": "r", "due": 10.0, "sent": 10.0, "status": 200, "tokens": [1, 2, 3],
            "times": [11.0, 11.1, 11.2], "complete": True, "cut": False, "done": 11.2,
            "max_tokens": 3, "prompt_len": 5}
    return dict(base, **kw)


@pytest.mark.parametrize("record,queue_is_load,fails", [
    (_rec(), False, False),
    # cut while still waiting for its first token: starved ...
    (_rec(status=0, tokens=[], times=[], complete=False, cut=True, done=None), False, True),
    (_rec(tokens=[], times=[], complete=False, cut=True, done=None), False, True),
    # ... unless waiting is the load of a mix above the knee
    (_rec(tokens=[], times=[], complete=False, cut=True, done=None), True, False),
    # cut while streaming: fine, unless it had gone silent
    (_rec(tokens=[1, 2], times=[99.8, 99.9], complete=False, cut=True, done=None), False, False),
    (_rec(tokens=[1, 2], times=[11.0, 11.1], complete=False, cut=True, done=None), False, True),
    (_rec(status=503, tokens=[], times=[], complete=False), False, True),
    (_rec(tokens=[1, 2], times=[11.0, 11.1]), False, True),          # fewer than asked
    (_rec(tokens=[1, 2, 3, 4], times=[11.0, 11.1, 11.2, 11.3]), False, True),
    (_rec(tokens=[1, 2, 99999]), False, True),                       # outside the vocabulary
    (_rec(complete=False), False, True),                             # broken stream
])
def test_what_counts_as_failed(record, queue_is_load, fails):
    from benchmark import serving

    healthy = [_rec(id=f"h{i}") for i in range(3)]
    bad = serving._structural_failures(healthy + [record], 50400, 100.0, queue_is_load)
    assert [rid for rid, _ in bad] == (["r"] if fails else [])


def test_gaps_and_tokens_are_those_that_arrived_inside_the_window():
    from benchmark import serving

    recs = [_rec(times=[9.0, 9.9, 10.2, 10.5, 20.1], tokens=[1] * 5, max_tokens=5)]
    gaps = serving.window_gaps(recs, (10.0, 20.0))
    assert gaps == [pytest.approx(0.3), pytest.approx(0.3)]
    run = {"kind": "serving", "records": recs, "window": (10.0, 20.0), "seconds": 10.0}
    assert H.load_metric("per_layer", "out_tokens_per_s").read(run) == pytest.approx(0.2)
    assert H.load_metric("end_to_end", "itl_p95_ms").read(run) == pytest.approx(300.0)


# -- manifest --------------------------------------------------------------------


def test_every_name_in_the_manifest_resolves_and_holds_allowed_characters():
    man = H.manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    e2e = {m["name"] for m in man["end_to_end"]}
    cells = {w["name"] for w in man["workloads"]}
    for c in man["configs"]:
        assert NAME.fullmatch(c["name"]) and os.path.exists(os.path.join(H.ROOT, c["file"]))
        cfg = H.load_config(man, c["name"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["source"] == c["source"]
        # ONE copy of the sizes: the published keys as run at the top, and
        # for each reduced key what the source has instead
        assert all(cfg[k] != cfg["reduced"][k]["published"] for k in c["reduced"])
        assert not {"published", "model", "reference"} & set(cfg)
        assert callable(H.family_piece(cfg, "model_config"))
    for w in man["workloads"]:
        assert NAME.fullmatch(w["name"]) and NAME.fullmatch(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        traffic = H.load_traffic(w["traffic"])
        assert hasattr(H.load_kind(traffic["kind"]), "run")
        H.load_config(man, w["config"])
        got = {m["name"] for m in H.metrics_for(man, "end_to_end", w["name"])}
        assert "setup_s" in got and len(got) >= 2
        assert H.metrics_for(man, "per_layer", w["name"])
    for section in ("end_to_end", "per_layer"):
        for m in man[section]:
            assert NAME.fullmatch(m["name"]), m["name"]
            assert UNIT.fullmatch(m["unit"]), m["unit"]
            assert m["better"] in ("lower", "higher")
            assert set(m.get("workloads", [])) <= cells
            assert callable(H.load_metric(section, m["name"]).read)
            if section == "per_layer":
                assert m["moves"] in e2e
                # reported only where the metric it moves is
                moved = next(x for x in man["end_to_end"] if x["name"] == m["moves"])
                where = set(m.get("workloads", cells))
                assert where <= set(moved.get("workloads", cells))
                if "roofline" in m["name"]:
                    assert m["name"].endswith("_roofline") and m["unit"] == "%"
    assert sum(w["chips"] == 4 for w in man["workloads"]) <= max(1, len(cells) // 4)
    # every reader file is listed; ``_common`` is the readers' shared helper
    listed = {m["name"] for m in man["per_layer"]} | {"_common"}
    files = {f[:-3] for f in os.listdir(os.path.join(H.BENCH_DIR, "layer_metrics"))
             if f.endswith(".py")}
    assert files == listed
    assert ({f[:-3] for f in os.listdir(os.path.join(H.BENCH_DIR, "end_to_end"))
             if f.endswith(".py")} == e2e)
    runs = 2 + 14 * 24
    assert runs * (man["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


# -- trace reduction -----------------------------------------------------------------


def test_short_names_keep_what_identifies_an_op():
    assert tr.short_name(
        '%fusion.4 = f32[1612800]{0:T(1024)S(1)} fusion(f32[32,50400]{1,0} %g.1), kind=kCustom'
    ) == "fusion.4 f32[1612800] fusion"
    assert tr.short_name(
        '%closed_call.9 = bf16[32,1,16,256]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call(s32[4096]{0} '
        '%b.2), custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    ) == "closed_call.9 bf16[32,1,16,256] custom-call tpu_custom_call"
    assert tr.short_name(
        '%checkpoint.18 = (bf16[416,1024,64]{2,1,0:T(8,128)(2,1)}, bf16[416,1024,64]{2,1,0}) '
        'custom-call(bf16[416,1024,64]{2,1,0} %b.3), custom_call_target="tpu_custom_call"'
    ) == "checkpoint.18 bf16[416,1024,64] custom-call tpu_custom_call"
    assert tr.short_name("while.3") == "while.3"
    assert tr.program_name("jit__decode_impl(1234567)") == "jit__decode_impl"


def test_union_and_leaves_on_a_hand_made_trace():
    assert tr.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    ops = [("while", 0.0, 10.0), ("fusion.1", 1.0, 2.0), ("kernel", 4.0, 5.0),
           ("copy", 20.0, 5.0)]
    assert [n for n, _, _ in tr.leaf_ops(ops)] == ["fusion.1", "kernel", "copy"]
    planes = {
        "/device:TPU:0": {
            tr.OPS_LINE: [(n, s * 1e9, d * 1e9) for n, s, d in ops],
            tr.MODULES_LINE: [("jit_step(123)", 0.0, 10e9), ("jit_step(123)", 20e9, 5e9)],
        },
        "/host:CPU": {"python": [("bench:next_batch", 11e9, 8e9), ("other", 0.0, 1e9)]},
    }
    red = tr.reduce_planes(planes)
    assert red["window_s"] == pytest.approx(25.0)
    assert red["busy_s"] == pytest.approx(15.0)
    dev = red["devices"]["/device:TPU:0"]
    assert dev["ops"]["jit_step/kernel"] == [1, pytest.approx(5.0)]
    assert not [k for k in dev["ops"] if "while" in k]
    assert dev["programs"] == {"jit_step": [pytest.approx(10.0), pytest.approx(5.0)]}
    assert dev["gaps"][0][1:] == [pytest.approx(10.0), "bench:next_batch"]
    assert red["host_spans"] == {"bench:next_batch": [1, pytest.approx(8.0)]}
    assert tr.time_of(red, r"kern|copy") == pytest.approx(10.0)
    assert tr.program_durations(red, "step") == [pytest.approx(10.0), pytest.approx(5.0)]
    bd = tr.breakdown(red)
    assert bd["device_ops"][0][0] in ("jit_step/kernel", "jit_step/copy")
    assert len(bd["device_ops"]) == 3
    assert bd["idle_gaps"] == [["bench:next_batch", pytest.approx(10.0)]]


@pytest.mark.parametrize("sample", ["trace_train_sample.json.gz", "trace_serve_sample.json.gz"])
def test_reduction_of_the_recorded_chip_trace(sample):
    """A slice of a trace recorded on the v5e (``make_trace_sample.py``);
    ``<sample>.expect.json`` holds what the reduction gave when the slice
    was looked at by hand."""
    path = os.path.join(HERE, "data", sample)
    with gzip.open(path, "rt") as f:
        planes = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                  for p, lines in json.load(f).items()}
    red = tr.reduce_planes(planes)
    want = H.load_json(path.replace(".json.gz", ".expect.json"))
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert 0 < red["busy_s"] <= red["window_s"]
    dev = red["devices"][sorted(red["devices"])[0]]
    for name, seconds in want["ops"].items():
        assert dev["ops"][name][1] == pytest.approx(seconds, rel=1e-6)
    # leaves never overlap, so their sum cannot exceed the busy time
    assert sum(row[1] for row in dev["ops"].values()) <= red["busy_s"] * (1 + 1e-6)
    for name, count in want["programs"].items():
        assert len(dev["programs"][name]) == count


# -- driven by data ----------------------------------------------------------------------


BIGRAM_FAMILY = '''
"""A family the harness has never seen: a bigram table, its own loss."""
import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class BigramConfig:
    vocab_size: int
    seq_len: int


def model_config(sizes):
    return BigramConfig(sizes["vocab"], sizes["context"])


def program_init():
    return lambda key, cfg: {"table": {"kernel": 0.01 * jax.random.normal(
        key, (cfg.vocab_size, cfg.vocab_size), jnp.float32)}}


def loss(cfg, params, tokens, mesh):
    logp = jax.nn.log_softmax(params["table"]["kernel"][tokens[:, :-1]])
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()


def reference_loss(params, tokens, cfg):
    return loss(cfg, params, jnp.asarray(tokens), None)
'''

SERVED_GPT_FAMILY = '''
"""A second unseen family: the program's GPT served through the engine
(the dummy's "reference" is the program's own float32 forward)."""


def model_config(sizes):
    from ray_tpu.models.gpt import GPTConfig

    return GPTConfig(vocab_size=sizes["vocab"], seq_len=sizes["context"],
                     d_model=sizes["width"], n_layers=sizes["depth"],
                     n_heads=sizes["heads"], dtype="float32")


SERVE_MODEL = "gpt"


def program_init():
    from ray_tpu.models.gpt import gpt_init

    return gpt_init


def reference_logits(params, tokens, rows, cfg):
    import jax.numpy as jnp

    from ray_tpu.models.gpt import gpt_forward

    return gpt_forward(cfg, params, jnp.asarray(tokens, jnp.int32)[None])[0][jnp.asarray(rows)]
'''


def test_configurations_of_unseen_families_mixes_cells_and_metrics_are_added_by_files_alone(
        tmp_path):
    """Copy the benchmark, add files and entries, edit nothing that was
    there: a TRAINED configuration of a family with its own config keys,
    model object, initializer, loss and reference, and a SERVED one of
    another family, each with a mix, a cell and metrics of its own."""
    root = tmp_path / "checkout"
    shutil.copytree(H.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    bench = root / "benchmark"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "families" / "bigram.py").write_text(BIGRAM_FAMILY)
    (bench / "families" / "served_gpt.py").write_text(SERVED_GPT_FAMILY)
    sizes = {"vocab": 256, "context": 96}
    (bench / "configs" / "dummy-bigram.json").write_text(json.dumps(dict(
        sizes, family="bigram", source="x", reduced={}, dtype="float32",
        train={"batch": 4, "learning_rate": 0.01, "weights_seed": 0},
        correctness={"probe_seed": 7, "probe_sequences": 2, "loss_tolerance": 1e-4},
        rehearsal={})))
    gptj = H.load_json(os.path.join(H.BENCH_DIR, "configs", "gptj-6b-l14-1chip.json"))
    (bench / "configs" / "dummy-served-gpt.json").write_text(json.dumps(dict(
        sizes, width=32, depth=2, heads=2, family="served_gpt", source="x", reduced={},
        deployment={"weights_seed": 0, "max_ongoing_requests": 16, "chips": 1},
        engine=gptj["rehearsal"]["engine"], correctness=gptj["rehearsal"]["correctness"],
        rehearsal={})))
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"extends": "pretrain-fixed-shape", "zipf_s": 1.2}))
    (bench / "traffic" / "dummy-chat.json").write_text(json.dumps(
        {"extends": "chat", "rate": 2, "rehearsal": {"rate": 4, "lead_s": 1, "drain_s": 10,
                                                     "prompt_len": {"median": 20, "sigma": 0.5, "min": 8, "max": 60},
                                                     "max_tokens": {"median": 5, "sigma": 0.3, "min": 3, "max": 8}}}))
    (bench / "layer_metrics" / "dummy_steps.py").write_text(
        "def read(run):\n    return float(run['train']['steps'])\n")
    (bench / "end_to_end" / "dummy_loss.py").write_text(
        "def read(run):\n    return run['train']['losses'][-1]\n")
    (bench / "end_to_end" / "dummy_tokens.py").write_text(
        "def read(run):\n    return float(sum(len(r['tokens']) for r in run['records']))\n")
    (bench / "layer_metrics" / "dummy_width.py").write_text(
        "def read(run):\n    return float(run['model']['d_model'])\n")
    man = H.manifest()
    for name in ("dummy-bigram", "dummy-served-gpt"):
        man["configs"].append({"name": name, "source": "x", "why": "y", "reduced": [],
                               "file": f"benchmark/configs/{name}.json"})
    man["workloads"] += [
        {"name": "dummy_train", "config": "dummy-bigram", "traffic": "dummy-mix",
         "chips": 1, "why": "z"},
        {"name": "dummy_serve", "config": "dummy-served-gpt", "traffic": "dummy-chat",
         "chips": 1, "why": "z"}]
    man["end_to_end"] += [
        {"name": "dummy_loss", "unit": "nat", "better": "lower", "bound": 0.05,
         "source": "host_clock", "workloads": ["dummy_train"]},
        {"name": "dummy_tokens", "unit": "tokens", "better": "higher", "bound": 0.05,
         "source": "host_clock", "workloads": ["dummy_serve"]}]
    man["per_layer"] += [
        {"name": "dummy_steps", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "train step",
         "moves": "dummy_loss", "workloads": ["dummy_train"]},
        {"name": "dummy_width", "unit": "1", "better": "higher",
         "source": "program_counter", "layer": "jitted steps",
         "moves": "dummy_tokens", "workloads": ["dummy_serve"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    for cell, trace, key in (("dummy_train", 0, "dummy_loss"), ("dummy_train", 1, "dummy_steps"),
                             ("dummy_serve", 0, "dummy_tokens"), ("dummy_serve", 1, "dummy_width")):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=H.ROOT)
        proc = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", cell,
             "--seed", "1", "--seconds", "2", "--trace", str(trace), "--rehearsal"],
            cwd=root, env=env, capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 3, proc.stderr[-2000:]
        lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
        assert lines[-1]["event"] == "rehearsal_result" and lines[-1]["correct"] is True
        assert key in lines[-1]["metrics"]
    after = {p: p.read_bytes() for p in bench.rglob("*")
             if p.is_file() and ".cache" not in p.parts and "__pycache__" not in p.parts}
    assert all(after[p] == data for p, data in before.items())


def test_the_harness_the_kinds_and_the_shared_readers_name_no_model_family():
    """What resolves a family is its name in the configuration's file."""
    import re as _re

    for rel in ("harness.py", "serving.py", "reference_check.py", "run.py", "client.py",
                "trace_reduce.py", "traffic_kinds/train_fixed_shape.py",
                "traffic_kinds/open_loop.py", "traffic_kinds/closed_sessions.py"):
        text = open(os.path.join(H.BENCH_DIR, rel)).read()
        assert not _re.search(r"gptj|gpt2|GPTJ|GPTConfig|gpt_init|gpt_loss|models\.gpt", text), rel


def _recorded(sample):
    with gzip.open(os.path.join(HERE, "data", sample), "rt") as f:
        planes = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                  for p, lines in json.load(f).items()}
    return tr.reduce_planes(planes)


def test_roofline_and_mfu_readers_take_their_counts_from_the_family():
    """On a CPU these readers have no peaks and return None; here they get
    the v5e's peaks, the recorded chip traces and the numbers of a chip
    run (PERF.md, PR 22)."""
    peaks = H.peaks_for("TPU v5 lite")
    cfg = H.load_json(os.path.join(H.BENCH_DIR, "configs", "gpt2-medium-train.json"))
    model = {"d_model": 1024, "n_layers": 24, "seq_len": 1024, "vocab_size": 50304}
    run = {"peaks": peaks, "config": cfg, "model": model, "device": {"count": 1}, "batch": 26,
           "reduced": _recorded("trace_train_sample.json.gz"),
           "train": {"steps": 68, "tokens_per_step": 26624, "t_open": 0.0,
                     "t_close": 68 * 26624 / 36461.88}}
    mfu = H.load_metric("per_layer", "mfu")
    assert mfu.read(run) == pytest.approx(42.05, abs=0.02)
    assert mfu.read(dict(run, peaks=None)) is None
    with pytest.raises(H.BenchFailure, match="train_flops_per_token"):
        mfu.read(dict(run, config={"family": "gptj"}))
    assert 5 < H.load_metric("per_layer", "flash_roofline").read(run) < 40

    cfg = H.load_json(os.path.join(H.BENCH_DIR, "configs", "gptj-6b-l14-1chip.json"))
    hbm = {"seq_bytes": 300 * 3670016, "block_bytes": 3670016}
    run = {"peaks": peaks, "config": cfg, "reduced": _recorded("trace_serve_sample.json.gz"),
           "model": {"d_model": 4096, "n_layers": 14}, "engine": {"block_size": 16, "tp": 1},
           "counters": {"trace_start": {"hbm": hbm}, "trace_stop": {"hbm": hbm}}}
    share = H.load_metric("per_layer", "paged_attn_roofline").read(run)
    assert 1 < share < 100
    assert H.load_metric("per_layer", "decode_step_dev_ms").read(run) > 10


@pytest.mark.parametrize("cell,trace,extra", [
    ("gptj_chat_r80_v2", 0, []), ("gptj_chat_r80_v2", 1, []),
    ("gptj_shared_prefix_sat", 0, []), ("gptj_shared_prefix_sat", 1, []),
    ("gpt2m_train", 0, []), ("gpt2m_train", 1, []),
    # what no cell lists yet and PERF.md keeps for later: the four-chip
    # configuration (four virtual devices here) and the multi-turn mix
    ("adhoc_tp4", 0, ["--adhoc", "gptj-6b-tp4,chat,4", "--set", "rate=3"]),
    ("adhoc_sessions", 0, ["--adhoc", "gptj-6b-l14-1chip,sessions48,1"]),
])
def test_rehearsal_runs_each_cell_end_to_end_and_prints_no_result_line(cell, trace, extra):
    proc = subprocess.run(
        [sys.executable, os.path.join(H.BENCH_DIR, "run.py"), "--workload", cell,
         "--seed", "3", "--seconds", "4", "--trace", str(trace), "--rehearsal"] + extra,
        cwd=H.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 3, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
    last = lines[-1]
    assert last["event"] == "rehearsal_result" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    # a CPU has no device trace, so a traced serving rehearsal may read none
    assert last["metrics"] or trace
    # the result line has no "event" key; a rehearsal never prints one
    assert all("event" in x for x in lines)


WRAPPER = """
import ctypes, os, subprocess, sys
ctypes.CDLL(None).prctl(36, 1, 0, 0, 0)  # subreaper: what outlives the run lands here
rc = subprocess.call(sys.argv[1:], stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
left = []
for pid in filter(str.isdigit, os.listdir("/proc")):
    try:
        stat = open(f"/proc/{pid}/stat").read()
    except OSError:
        continue
    state, ppid = stat.rsplit(")", 1)[1].split()[:2]
    if int(ppid) == os.getpid():
        left.append((pid, state))
print(rc, left)
"""


def test_a_run_leaves_no_process_behind_not_even_a_dead_one():
    """The backlog cell ends with the replica busy and 120 connections cut;
    whatever the run started has ended, and has been waited for, before
    the run's own process exits."""
    proc = subprocess.run(
        [sys.executable, "-c", WRAPPER, sys.executable, os.path.join(H.BENCH_DIR, "run.py"),
         "--workload", "gptj_shared_prefix_sat", "--seed", "1", "--seconds", "3",
         "--trace", "0", "--rehearsal"],
        cwd=H.ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.stdout.strip() == "3 []", proc.stdout + proc.stderr[-1000:]


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(H.BENCH_DIR, "run.py"), "--workload", "gpt2m_train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=H.ROOT, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert not [x for x in proc.stdout.splitlines() if x.startswith("{")]
