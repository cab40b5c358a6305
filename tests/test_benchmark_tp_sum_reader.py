"""``benchmark/layer_metrics/tp_sum_dev_ms.py`` on a hand-made trace: the
reader sums the decode programs' leaf ops under the ``tp_sum`` scope per
decode, splits them by the nested scope on a progress line, and finds
nothing (None, no exception) in a program without the scope or a run
without a trace: the parent of the PR that added the nested scopes is
measured with this reader too."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness as H  # noqa: E402

PRE = "jit(_decode_impl)/shard_map/while/body/closed_call/"
GATHER = "%all-gather.7 = bf16[256,4096]{1,0} all-gather(bf16[64,4096]{1,0} %fusion.196)"
ADD = "%fusion.12 = bf16[64,4096]{1,0} fusion(bf16[256,4096]{1,0} %all-gather.7), kind=kLoop"
MLP = "%fusion.3 = bf16[64,4096]{1,0} fusion(bf16[64,4096]{1,0} %p.1), kind=kOutput"


def _trace(nested: bool):
    """Two decodes of 10 ms and one prefill; ns.  In each decode a gather of
    0.2 ms, adds of 0.1 ms and an MLP op; the prefill's gather is not decode's."""
    ops, modules = [], []
    for i, prog in enumerate(["jit__decode_impl", "jit__prefill_impl", "jit__decode_impl"]):
        t = i * 20e6
        modules.append((t, t + 10e6, prog))
        ops += [(GATHER, t + 1e6, 0.2e6), (ADD, t + 2e6, 0.1e6), (MLP, t + 3e6, 5e6)]
    mid = ("gather/", "add/") if nested else ("", "")
    return {
        "ops": ops, "modules": modules, "spans": [("llm.step", 0.0, 1.0)],
        "op_names": {GATHER: PRE + "tp_sum/" + mid[0] + "all_gather",
                     ADD: PRE + "tp_sum/" + mid[1] + "add",
                     MLP: PRE + "mlp/dot_general"},
    }


@pytest.fixture()
def reader(monkeypatch):
    mod = H.load_metric("per_layer", "tp_sum_dev_ms")
    monkeypatch.setattr(mod, "load", lambda run: run.get("spans"))
    monkeypatch.setattr(mod.trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(mod, "read_trace", lambda path: path)
    return mod


@pytest.mark.parametrize("nested,split", [
    (True, {"gather": 0.2, "add": 0.1}), (False, {"tp_sum": 0.3}),
])
def test_tp_sum_ms_per_decode_and_its_split(reader, capsys, nested, split):
    run = {"spans": {"decodes": 2}, "trace_dir": _trace(nested)}
    assert reader.read(run) == pytest.approx(0.3)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["event"] == "program_spans" and line["scope"] == "tp_sum"
    assert line["ms_per_decode_by_scope"] == pytest.approx(split)


def test_nothing_to_read_is_none(reader):
    assert reader.read({"spans": None}) is None  # no trace, or no llm.* span in it
    assert reader.read({"spans": {"decodes": 0}}) is None
    unscoped = _trace(True)
    unscoped["op_names"] = {}
    assert reader.read({"spans": {"decodes": 2}, "trace_dir": unscoped}) is None
