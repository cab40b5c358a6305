"""The reader ``moe_batch_form_share`` (ISSUE 60): the share of a decode's
touched experts that went through the expert layer's batch form, from the
device's own two counts; nothing to read from a program that does not count
the form's steps; listed where it finds something to read."""

import pytest

from benchmark import harness as H

NAME = "moe_batch_form_share"
#: ``stats()["moe"]`` at a window's two ends, as both families give it
OPEN = {"decodes": 1003, "decode_pairs": 800000, "decode_touched": 327000,
        "decode_expert_steps": 327000}
CLOSE = {"decodes": 1153, "decode_pairs": 920000, "decode_touched": 376050,
         "decode_expert_steps": 376050}


def _read(open_, close):
    return H.load_metric("per_layer", NAME).read(
        {"counters": {"open": {"moe": open_}, "close": {"moe": close}}})


def _without(counts, *names):
    return {k: v for k, v in counts.items() if k not in names}


@pytest.mark.parametrize("steps,share", [
    (376050, 100.0),                    # every decode of 16 rows: the batch form alone
    (327000, 0.0),                      # a window of tile-loop decodes adds nothing
    (327000 + 49050 // 2, 50.0),
])
def test_the_share_is_the_forms_steps_over_the_touched_experts(steps, share):
    assert _read(OPEN, dict(CLOSE, decode_expert_steps=steps)) == pytest.approx(share)


@pytest.mark.parametrize("open_,close", [
    # the parent: a program that counts no steps of the form
    (_without(OPEN, "decode_expert_steps"), _without(CLOSE, "decode_expert_steps")),
    (OPEN, OPEN),                       # no decode in the window
    ({}, {}),                           # a family without an expert layer
], ids=["parent", "no_decode", "no_moe"])
def test_nothing_to_read_is_none_and_does_not_raise(open_, close):
    assert _read(open_, close) is None


def test_a_run_without_counters_reads_none():
    read = H.load_metric("per_layer", NAME).read
    assert read({"counters": None}) is None
    assert read({"counters": {"open": {}, "close": {}}}) is None


def test_it_is_listed_beside_the_tile_fill_in_the_two_expert_cells():
    entry = next(m for m in H.manifest()["per_layer"] if m["name"] == NAME)
    fill = next(m for m in H.manifest()["per_layer"] if m["name"] == "moe_tile_fill_share")
    assert entry["workloads"] == ["kimi_longdoc_sat", "graniteh_draft_sat"]
    assert {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")} == {
        k: fill[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert H.manifest()["per_layer"][-1] == entry   # appended: nothing before it moved
