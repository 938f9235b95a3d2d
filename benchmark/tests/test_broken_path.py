"""A run whose timed path is broken underneath must say ``correct:
false``. These tests skip the harness's look for a chip (a CPU
rehearsal), drive the rest of a run in this process, and break the
program where it produces its answers."""

import json

import pytest

from benchmark import contract, run

BENCH = contract.load_benchmark()


def last_line(capsys, cell: str, seed: int) -> dict:
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "4",
                   "--trace", "0", "--rehearse-cpu"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    contract.validate(line, BENCH, cell, False)
    return line


@pytest.fixture
def altered_aggregates(monkeypatch):
    """Every aggregate an engine hands back is off by one in its last
    column: an approximate answer where the configuration says exact."""
    from yugabyte_db_tpu.storage.tpu_engine import TpuStorageEngine

    sound = TpuStorageEngine.scan_batch

    def scan_batch(self, specs, deadline=None):
        results = sound(self, specs, deadline=deadline)
        for spec, res in zip(specs, results):
            if spec.is_aggregate and res.rows and \
                    isinstance(res.rows[0][-1], int):
                res.rows[0] = res.rows[0][:-1] + (res.rows[0][-1] + 1,)
        return results

    monkeypatch.setattr(TpuStorageEngine, "scan_batch", scan_batch)


def test_sound_run_is_correct(capsys):
    assert last_line(capsys, "tpch_power_q1q6", 11)["correct"] is True


def test_altered_aggregate_is_not_correct(capsys, altered_aggregates):
    line = last_line(capsys, "tpch_power_q1q6", 12)
    assert line["correct"] is False and line["failed"] > 0
    wrong, limit = line["compared"]["answers_wrong"]
    assert wrong > limit == 0
