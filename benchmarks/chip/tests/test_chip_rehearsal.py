"""Each driver end to end on the CPU at a tiny size, sound and with a
fault planted under the timed path."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import rehearsal  # noqa: E402


@pytest.mark.parametrize("kind", ["lm", "cnn"])
def test_sound_run_is_correct(kind, capsys):
    rec = rehearsal.rehearse(kind)
    assert rec["correct"], rec["checks"]
    assert rec["failed"] == 0 and rec["attempted"] > 0
    assert rec["compiles_in_window"] == 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("kind,fault", [
    ("lm", "stale_state"), ("lm", "token_altered"), ("lm", "half_batch"),
    ("cnn", "answer_altered"), ("cnn", "half_batch")])
def test_fault_is_caught(kind, fault):
    rec = rehearsal.rehearse(kind, fault=fault)
    assert not rec["correct"], rec["checks"]


def test_cnn_window_keeps_calls_in_flight_and_waits_for_each():
    """The CNN window dispatches ahead of the call it waits for, and
    closes only once every call it dispatched has ended."""
    rec = rehearsal.rehearse("cnn", seconds=1.0)
    assert rec["in_flight"] > 1
    assert rec["calls"] > rec["in_flight"]
    assert rec["attempted"] == rec["calls"] * rec["batch"]
    assert rec["window_s"] >= rec["seconds"]
    assert rec["correct"], rec["checks"]
