"""bench.py's pieces that outlive the backend probe: the one-shot report, the
peak table, and the refusal to run without a chip (the probe child, its
state machine and the CPU fall-back went in PR 23 — one process per chip,
and a CPU run is never a result)."""

import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402


def test_init_backend_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        bench._init_backend()
    assert exc.value.code not in (0, None)
    assert "cpu" in capsys.readouterr().err


@pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197e12),
                                       ("TPU v5e", 197e12),
                                       ("TPU v5p", 459e12),
                                       ("TPU v4", 275e12)])
def test_peak_table_is_the_published_bf16_peak(kind, peak):
    assert bench.peak_flops(kind) == peak


class TestOneShotReport:
    """The wall-clock-budget contract: exactly one JSON line, no matter
    which thread (main path or watchdog) reaches the deadline first."""

    def test_emits_once(self, capsys):
        rec = {"value": 1}
        rep = bench._OneShotReport(rec)
        assert rep.emit() is True
        assert rep.emit() is False          # second caller loses the race
        out = capsys.readouterr().out.strip().splitlines()
        assert len(out) == 1
        import json
        got = json.loads(out[0])
        assert got["value"] == 1
        # emit stamps the per-phase checkpoint bookkeeping as complete
        assert got["partial"] == {"complete": True, "phases_done": []}

    def test_phase_checkpoints_survive_on_disk(self, tmp_path, capsys):
        # per-phase atomic checkpoints: a SIGKILL landing after a phase
        # completed must leave that phase's results parseable on disk
        # (BENCH_r05: rc=124, empty tail, everything lost)
        import json
        path = str(tmp_path / "partial.json")
        rec = {"value": 3}
        rep = bench._OneShotReport(rec, path=path)
        rep.checkpoint("warm_up")
        rec["value"] = 7                    # later phase updates the dict
        rep.checkpoint("timed_passes")
        with open(path, encoding="utf-8") as fh:
            got = json.load(fh)
        assert got["value"] == 7
        assert got["partial"] == {
            "complete": False, "phases_done": ["warm_up", "timed_passes"]}
        rep.emit()
        with open(path, encoding="utf-8") as fh:
            got = json.load(fh)
        assert got["partial"]["complete"] is True
        # post-emit checkpoints are no-ops: the final record stays
        rep.checkpoint("late")
        with open(path, encoding="utf-8") as fh:
            assert json.load(fh)["partial"]["complete"] is True

    def test_in_place_mutation_is_visible(self, capsys):
        # main() must update the shared dict in place (never rebind it):
        # the watchdog holds a reference to the original object
        rec = {"value": 0}
        rep = bench._OneShotReport(rec)
        rec["value"] = 42
        rec["stage_counters"] = {"h2d": {"calls": 1}}
        rep.emit()
        import json
        got = json.loads(capsys.readouterr().out)
        assert got["value"] == 42
        assert got["stage_counters"]["h2d"]["calls"] == 1

    def test_concurrent_emit_single_line(self, capsys):
        import threading
        rep = bench._OneShotReport({"x": 1})
        wins = []
        ts = [threading.Thread(target=lambda: wins.append(rep.emit()))
              for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert sum(wins) == 1
        assert len(capsys.readouterr().out.strip().splitlines()) == 1
