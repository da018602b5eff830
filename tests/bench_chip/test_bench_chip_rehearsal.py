"""Every cell end to end at tiny sizes on the CPU, and the refusal to
measure anywhere but on a known TPU."""
from __future__ import annotations

import json

import pytest

from benchmarks.chip import harness

CELLS = [w["name"] for w in harness.load_spec()["workloads"]]


def run(capsys, *argv) -> tuple[int, dict | None]:
    rc = harness.main(list(argv))
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_the_cell_and_prints_no_metric(capsys, cell):
    rc, res = run(capsys, "--workload", cell, "--seed", str(2**31 + 3),
                  "--seconds", "1", "--rehearse")
    assert rc == 0
    assert res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "metrics" not in res and "device" not in res
    assert list(res)[-1] == "check"
    for name, c in res["check"].items():
        assert c["value"] is not None and c["limit"] is not None, name


def test_a_cpu_is_refused_and_nothing_is_printed(capsys):
    rc, res = run(capsys, "--workload", CELLS[0], "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert rc != 0 and res is None


def test_an_unknown_device_kind_is_refused(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v0 imaginary"

    monkeypatch.setattr(harness.jax, "devices", lambda *a: [Dev()])
    with pytest.raises(harness.NoChip):
        harness.device_info(1)


def test_too_few_chips_are_refused(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(harness.jax, "devices", lambda *a: [Dev()])
    assert harness.device_info(1)[0]["kind"] == "TPU v5 lite"
    with pytest.raises(harness.NoChip):
        harness.device_info(4)

