"""The tier-1 durations artifact (tests/conftest.py): the controller appends every
report's seconds as it arrives, so a run the clock cuts leaves what it reached.
Exercised by driving the hook functions directly against a stub session — a real
nested pytest run would cost more than the hook saves."""

import json
import types

import tests.conftest as harness


def _stub_session(rootpath):
    config = types.SimpleNamespace(rootpath=rootpath)  # no workerinput attr
    return types.SimpleNamespace(config=config)


def _stub_report(nodeid, when, duration, outcome="passed"):
    return types.SimpleNamespace(nodeid=nodeid, when=when, duration=duration, outcome=outcome)


def _rows(path):
    return [json.loads(line) for line in path.read_text().splitlines()]


def test_durations_artifact_holds_every_report_as_it_arrives(tmp_path, monkeypatch):
    artifact = tmp_path / "durations.jsonl"
    artifact.write_text('{"nodeid": "an older run"}\n')
    monkeypatch.setattr(harness, "_durations_path", None)
    monkeypatch.setenv("MODALITIES_TPU_TEST_DURATIONS_PATH", str(artifact))
    harness.pytest_sessionstart(_stub_session(tmp_path))
    assert artifact.read_text() == ""  # a run's file holds that run alone

    harness.pytest_runtest_logreport(_stub_report("t/a.py::slow", "setup", 99.0))
    harness.pytest_runtest_logreport(_stub_report("t/a.py::slow", "call", 3.5, "failed"))
    # no session end is needed: a run cut here has both lines
    assert _rows(artifact) == [
        {"nodeid": "t/a.py::slow", "when": "setup", "duration_s": 99.0, "outcome": "passed"},
        {"nodeid": "t/a.py::slow", "when": "call", "duration_s": 3.5, "outcome": "failed"},
    ]
    harness.pytest_runtest_logreport(_stub_report("t/a.py::fast", "call", 0.0104))
    assert [(r["nodeid"], r["duration_s"]) for r in _rows(artifact)][2:] == [("t/a.py::fast", 0.01)]


def test_durations_artifact_disable_and_xdist_worker_skip(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "_durations_path", None)
    monkeypatch.setenv("MODALITIES_TPU_TEST_DURATIONS_PATH", "")  # "" disables
    harness.pytest_sessionstart(_stub_session(tmp_path))
    harness.pytest_runtest_logreport(_stub_report("t::x", "call", 1.0))
    assert list(tmp_path.iterdir()) == []

    monkeypatch.delenv("MODALITIES_TPU_TEST_DURATIONS_PATH")
    worker = _stub_session(tmp_path)
    worker.config.workerinput = {"workerid": "gw0"}  # xdist worker: its reports reach the controller's hook
    harness.pytest_sessionstart(worker)
    harness.pytest_runtest_logreport(_stub_report("t::x", "call", 1.0))
    assert list(tmp_path.iterdir()) == []

    # default path lands at <rootdir>/test_durations.jsonl
    harness.pytest_sessionstart(_stub_session(tmp_path))
    harness.pytest_runtest_logreport(_stub_report("t::x", "call", 1.0))
    assert [r["nodeid"] for r in _rows(tmp_path / "test_durations.jsonl")] == ["t::x"]

    # an unwritable path never fails the suite
    monkeypatch.setenv("MODALITIES_TPU_TEST_DURATIONS_PATH", str(tmp_path / "no" / "such" / "dir.jsonl"))
    harness.pytest_sessionstart(_stub_session(tmp_path))
    harness.pytest_runtest_logreport(_stub_report("t::y", "call", 1.0))
    assert harness._durations_path is None
