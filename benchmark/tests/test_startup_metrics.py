"""The six ``setup_*`` per-layer metrics (ISSUE 56): a traced rehearsal of
a serving cell and of the train cell reads every one its ``workloads``
lists name, each a finite number of seconds no larger than the run's
``setup_s``, the parts under the whole; a program that keeps no start-up
ledger (a parent commit) gives every reader None, never 0.  CPU: the
seconds are a CPU's, the control flow is the chip's."""

import json
import math
import os
import subprocess
import sys

import pytest

from benchmark import harness as H

NEW = ("setup_replica_init_s", "setup_backend_init_s", "setup_weights_s",
       "setup_warmup_s", "setup_worker_boot_s", "setup_compile_s")


def _rehearse(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(H.BENCH_DIR, "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "4", "--trace", "1", "--rehearsal"],
        cwd=H.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 3, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
    assert lines[-1]["event"] == "rehearsal_result" and lines[-1]["correct"] is True
    ledger = [x for x in lines if x["event"] == "startup_ledger"]
    assert len(ledger) == 1  # six readers, one line
    return lines[-1]["metrics"], ledger[0]


@pytest.mark.parametrize("cell", ["gptj_shared_prefix_sat", "gpt2m_train"])
def test_a_traced_rehearsal_reads_the_cells_setup_metrics(cell):
    metrics, led = _rehearse(cell)
    named = {m["name"] for m in H.metrics_for(H.manifest(), "per_layer", cell)} & set(NEW)
    assert named == (set(NEW) if cell != "gpt2m_train"
                     else {"setup_worker_boot_s", "setup_compile_s"})
    got = {k: metrics[k] for k in named}  # a KeyError names the one left out
    for name, m in got.items():
        assert m["unit"] == "s" and math.isfinite(m["value"]), (name, m)
        assert 0.0 <= m["value"] <= led["setup_s"], (name, m, led["setup_s"])
    cc = led["compile_cache"]
    assert got["setup_compile_s"]["value"] == (
        cc["trace_s"] + cc["lower_s"] + cc["backend_compile_s"])
    assert cc["programs"] > 0 and cc["by_fn"]
    if cell == "gpt2m_train":
        assert got["setup_worker_boot_s"]["value"] == led["phases_s"]["worker_boot"]
        return
    parts = sum(got[k]["value"] for k in ("setup_worker_boot_s", "setup_backend_init_s",
                                          "setup_weights_s", "setup_warmup_s"))
    assert parts <= got["setup_replica_init_s"]["value"] <= led["setup_s"]
    whole = led["t_ready"] - led["t_init_begin"]
    assert led["phases_s"]["other"] < 0.1 * whole
    # the replica's worker met jax while it unpickled its actor's class
    assert 0.0 < led["phases_s"]["import"] <= led["phases_s"]["worker_boot"]
    assert {"prefill", "decode"} <= set(led["first_call"])


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("report", [
    None, {}, {"compile_cache": {"dir": None, "requests": 3, "hits": 3}},
    {"startup": None, "compile_cache": {"requests": 0, "hits": 0}},
], ids=["no_report", "empty", "parent", "null"])
def test_a_program_without_the_ledger_reads_as_nothing(name, report):
    run = {"setup_s": 50.0}
    if report is not None:
        run["device_report"] = report
    assert H.load_metric("per_layer", name).read(run) is None


def test_every_new_entry_names_setup_s_and_comes_last():
    per_layer = H.manifest()["per_layer"]
    assert [m["name"] for m in per_layer[-len(NEW):]] == list(NEW)
    for m in per_layer[-len(NEW):]:
        assert (m["moves"], m["source"], m["layer"], m["better"], m["unit"]) == (
            "setup_s", "host_clock", "start-up", "lower", "s")
    assert not [m for m in per_layer[:-len(NEW)] if m["moves"] == "setup_s"]
