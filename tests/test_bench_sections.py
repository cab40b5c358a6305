"""bench.py cannot lie: a failed section fails the run.

Every section runs behind ``bench._section``, which emits the section's
own JSON line the moment it finishes.  A section that raises or returns
nothing propagates — no retry, no record with value 0, no exit code 0 —
the training headline refuses to run without a TPU, and the peak table
rejects a device it does not list.
"""

import contextlib
import io
import json
import os
import subprocess
import sys
import types

import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

import bench  # noqa: E402


def _run(sections, name, fn):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        result = bench._section(sections, name, fn)
    lines = [ln for ln in buf.getvalue().splitlines() if ln.startswith("{")]
    return result, lines


def test_section_success_emits_its_own_line():
    sections = {}
    result, lines = _run(sections, "good", lambda: {"value": 7})
    assert result == {"value": 7}
    assert sections["good"] == {"section": "good", "ok": True}
    assert json.loads(lines[-1]) == {"section": "good", "ok": True}


def test_section_failure_propagates_without_retry():
    sections = {}
    calls = []

    def boom():
        calls.append(1)
        raise OSError("compile failed")

    with pytest.raises(OSError, match="compile failed"):
        _run(sections, "exploding", boom)
    assert len(calls) == 1 and "exploding" not in sections


def test_section_empty_result_is_a_failure():
    """Subprocess-wrapped sections whose child printed no record return
    nothing — that is a failed section, not an empty success."""
    sections = {}
    with pytest.raises(RuntimeError, match="produced no result"):
        _run(sections, "empty", dict)
    assert "empty" not in sections


def test_failing_section_gives_nonzero_exit_and_no_headline():
    """End to end: ``python bench.py`` with its first section failing
    exits non-zero and prints no headline record."""
    code = (
        "import bench\n"
        "def boom():\n"
        "    raise RuntimeError('section down')\n"
        "bench._core_microbench = boom\n"
        "bench.main()\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=_REPO_ROOT, capture_output=True,
        text=True, timeout=60,
    )
    assert out.returncode != 0
    assert "section down" in out.stderr
    assert "gpt_train_tokens_per_sec_per_chip" not in out.stdout


def test_peak_for_known_kind():
    dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert bench._peak_for(dev) == 197e12


@pytest.mark.parametrize("kind", ["cpu", "TPU v99", "NVIDIA H100"])
def test_peak_for_unknown_kind_raises(kind):
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        bench._peak_for(types.SimpleNamespace(device_kind=kind))


def test_train_headline_refuses_a_cpu():
    """No d_model-128 stand-in: without a TPU the headline is an error."""
    with pytest.raises(RuntimeError, match="measures a TPU"):
        bench._train_headline()
