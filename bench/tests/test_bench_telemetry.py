"""The readers of the engine's step log, on a synthetic run and log: each
reads the window's records, and stays silent where the log's count
differs from the window's steps or where the program keeps no log."""
import re
import sys
from collections import deque
from types import SimpleNamespace

import pytest

from bench import harness
from repro.serve import telemetry

#: phase lengths, ms, of every synthetic step: refill, assemble, dispatch,
#: wait, fetch, commit
PHASE_MS = (0.1, 0.2, 0.3, 20.0, 2.0, 0.4)
#: the caller's time between two steps, ms
BETWEEN_MS = 0.5


def _log(n, t=100.0):
    recs = []
    for _ in range(n):
        stamps = [t]
        for ms in PHASE_MS:
            stamps.append(stamps[-1] + ms / 1e3)
        recs.append(telemetry.StepRecord(*stamps, "decode", 1, 0, 4, 0))
        t = stamps[-1] + BETWEEN_MS / 1e3
    return recs


def _run(n_window_steps, recs):
    window = SimpleNamespace(t_open=recs[0].t0 - 1e-3,
                             t_close=recs[-1].t1 + 1e-3,
                             steps=[None] * n_window_steps)
    return SimpleNamespace(window=window)


@pytest.fixture
def log(monkeypatch):
    recs = _log(5)
    before = _log(3, t=50.0)                      # before the window opens
    monkeypatch.setattr(telemetry, "LOG", deque(before + recs, maxlen=64))
    return recs


def _after(note, label):
    """The number that follows ``label`` in a reader's note."""
    return float(re.match(r"[-+.0-9e]+", note.split(label, 1)[1]).group())


def test_host_ms_per_step_reads_the_host_phases(log):
    value, note = harness.read_metric("host_ms_per_step", _run(5, log))
    assert value == pytest.approx(0.1 + 0.2 + 0.3 + 0.4)
    assert note.startswith("5 steps")
    for name, ms in zip(telemetry.PHASES, PHASE_MS):
        assert _after(note, name + " ") == pytest.approx(ms)
    assert _after(note, "caller between steps ") == pytest.approx(BETWEEN_MS)


def test_fetch_ms_per_step_reads_the_fetch_phase(log):
    value, note = harness.read_metric("fetch_ms_per_step", _run(5, log))
    assert value == pytest.approx(2.0)
    assert _after(note, "serve.wait ") == pytest.approx(20.0)


@pytest.mark.parametrize("name", ["host_ms_per_step", "fetch_ms_per_step"])
def test_a_count_that_differs_from_the_window_is_silent(log, name):
    assert harness.read_metric(name, _run(4, log)) is None
    assert harness.read_metric(name, _run(6, log)) is None


@pytest.mark.parametrize("name", ["host_ms_per_step", "fetch_ms_per_step"])
def test_a_program_without_a_step_log_is_silent(log, name, monkeypatch):
    """As on a program that predates the step log: the import fails, the
    reader returns None and does not raise."""
    monkeypatch.setitem(sys.modules, "repro.serve.telemetry", None)
    monkeypatch.delattr(sys.modules["repro.serve"], "telemetry")
    assert harness.read_metric(name, _run(5, log)) is None
