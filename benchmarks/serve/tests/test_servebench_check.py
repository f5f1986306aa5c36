"""The numbers ``correct`` is decided on, and the count of failed
requests, by hand."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from servebench import run  # noqa: E402


def test_readings_by_hand():
    per_req = [(np.array([0.0, 0.02, 0.0]), np.array([0.01, 0.05, 0.5])),
               (np.array([0.0, 0.04]), np.array([0.3, 0.08]))]
    r = run.compared(per_req, near_tie=0.1)
    assert r["max_gap"] == pytest.approx(0.04)
    assert r["mean_gap"] == pytest.approx(0.06 / 5)
    assert r["gap_per_near_tie"] == pytest.approx(0.06 / 3)


def test_no_near_tie_divides_by_one():
    r = run.compared([(np.array([0.0, 0.5]), np.array([1.0, 2.0]))], 0.1)
    assert r["gap_per_near_tie"] == pytest.approx(0.5)


def test_verdict():
    readings = {"max_gap": 9.0, "mean_gap": 0.001, "gap_per_near_tie": 0.02}
    limits = {"mean_gap": 0.0015, "gap_per_near_tie": None}
    assert run.verdict(readings, limits, 10, 0) is True
    assert run.verdict(readings, limits, 10, 1) is False
    assert run.verdict(readings, limits, 0, 0) is False
    assert run.verdict(readings, {"mean_gap": None}, 10, 0) is False
    assert run.verdict(readings, {"gap_per_near_tie": 0.01}, 10, 0) is False


def _req(asked, got, finished=True):
    rec = SimpleNamespace(finish=1.0 if finished else None)
    return run.ReqLog(0.0, 0.0, 8, asked, 0,
                      req=SimpleNamespace(rec=rec, generated=got))


def test_failed_counts_requests_served_wrongly():
    log = run.HostLog(requests=[
        _req(3, [1, 2, 3]),              # served in full
        _req(3, [1, 2], finished=False),  # still decoding at the close
        _req(3, [1, 2]),                 # finished short
        _req(2, [1, 99]),                # a token outside the vocabulary
        run.ReqLog(0.0, 0.0, 8, 3, 1)])  # never reached the engine's output
    assert run.serving_failures(log, vocab=50) == 2
