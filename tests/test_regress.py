"""Tests for the benchmark regression gate (repro.obs.regress +
benchmarks/check_regression.py).

The gate's contract: benchmark reports carry a normalised ``metrics``
block (falling back to legacy key extraction for committed baselines),
``compare`` turns a baseline/fresh pair into per-metric deltas with
tolerance bands, and the CLI exits non-zero exactly when a gated metric
regressed.
"""
import json
import pathlib
import subprocess
import sys

import pytest

from repro.obs import regress

ROOT = pathlib.Path(__file__).resolve().parents[1]
CHECK = ROOT / "benchmarks" / "check_regression.py"


def _report(**metrics):
    return {"metrics": {k: regress.metric(*v) if isinstance(v, tuple)
                        else regress.metric(v) for k, v in metrics.items()}}


# ---------------------------------------------------------------------------
# metric extraction
# ---------------------------------------------------------------------------

def test_metric_constructor_defaults():
    m = regress.metric(1.5)
    assert m == {"value": 1.5, "unit": "s", "direction": "lower"}
    m = regress.metric(4.0, "x", "higher", tolerance=2.0)
    assert m["direction"] == "higher" and m["tolerance"] == 2.0


def test_metrics_from_report_prefers_embedded_block():
    rep = _report(**{"a.t": 1.0})
    rep["forward_grad"] = {"warm_s": 9.9}       # legacy key must be ignored
    got = regress.metrics_from_report(rep)
    assert set(got) == {"a.t"}


@pytest.mark.parametrize("name", [
    "BENCH_db_mnist.json", "BENCH_db_mnist_duckdb.json",
    "BENCH_array_vs_rel.json", "BENCH_zoo_db.json", "BENCH_ssm_db.json",
])
def test_committed_baselines_yield_metrics(name):
    path = ROOT / name
    if not path.exists():
        pytest.skip(f"{name} not committed")
    got = regress.metrics_from_report(json.loads(path.read_text()))
    assert got, f"no metrics extracted from {name}"
    for m in got.values():
        assert m["direction"] in ("lower", "higher")
        assert isinstance(m["value"], (int, float))


def test_legacy_mnist_extraction_backend_prefixed_keys():
    rep = {
        "config": {"backend": "sqlite"},
        "ingestion": {"speedup": 3.0},
        "forward_grad": {"sqlite_warm_s": 0.2, "sqlite_cold_s": 0.5,
                         "fused_speedup": 1.4},
        "training": {"recursive_per_iter_s": 0.1},
        "trace": {"train_iteration": {"attribution": 0.97}},
    }
    got = regress.metrics_from_report(rep)
    assert got["forward_grad.warm_s"]["value"] == 0.2
    assert got["forward_grad.cold_s"]["value"] == 0.5
    assert got["trace.train_attribution"]["direction"] == "higher"
    assert got["ingestion.pivot_speedup"]["direction"] == "higher"


# ---------------------------------------------------------------------------
# compare semantics
# ---------------------------------------------------------------------------

def test_compare_identity_is_all_ok():
    rep = _report(**{"a.t": 1.0, "b.speedup": (4.0, "x", "higher")})
    deltas = regress.compare(rep, rep)
    assert all(d.status == "ok" for d in deltas)
    assert not any(d.failed for d in deltas)


def test_compare_flags_lower_metric_slowdown():
    base = _report(**{"train.s": 1.0})
    fresh = _report(**{"train.s": 2.0})
    d, = regress.compare(base, fresh)
    assert d.status == "regressed" and d.failed
    assert d.ratio == pytest.approx(2.0)
    # within the tolerance band it is only "warn", never a failure
    d, = regress.compare(base, _report(**{"train.s": 1.4}))
    assert d.status in ("ok", "warn") and not d.failed


def test_compare_flags_higher_metric_drop():
    base = _report(**{"fused.speedup": (3.0, "x", "higher")})
    fresh = _report(**{"fused.speedup": (1.0, "x", "higher")})
    d, = regress.compare(base, fresh)
    assert d.status == "regressed" and d.failed
    # gate_directions excludes "higher" → skipped, not failed (smoke mode)
    d, = regress.compare(base, fresh, gate_directions=("lower",))
    assert d.status == "skipped" and not d.failed


def test_compare_per_metric_tolerance_override():
    base = _report(**{"noisy.s": (1.0, "s", "lower", 3.0)})
    fresh = _report(**{"noisy.s": (2.5, "s", "lower", 3.0)})
    d, = regress.compare(base, fresh, tolerance=1.5)
    assert d.status != "regressed"          # 3.0 override beats global 1.5


def test_compare_missing_and_new_metrics():
    base = _report(**{"gone.s": 1.0, "kept.s": 1.0})
    fresh = _report(**{"kept.s": 1.0, "added.s": 2.0})
    by_name = {d.name: d for d in regress.compare(base, fresh)}
    assert by_name["gone.s"].status == "missing" and by_name["gone.s"].failed
    assert by_name["added.s"].status == "new"
    deltas = regress.compare(base, fresh, fail_on_missing=False)
    assert not any(d.failed for d in deltas)


def test_delta_table_renders_every_row():
    base = _report(**{"a.s": 1.0, "b.s": 1.0})
    fresh = _report(**{"a.s": 1.0, "b.s": 5.0})
    text = regress.delta_table(regress.compare(base, fresh), title="t")
    assert "a.s" in text and "b.s" in text and "regressed" in text
    assert "5.00" in text or "5.0" in text


# ---------------------------------------------------------------------------
# the CLI gate
# ---------------------------------------------------------------------------

def _run_cli(*args):
    env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin"}
    return subprocess.run([sys.executable, str(CHECK), *args],
                          capture_output=True, text=True, env=env)


def test_cli_passes_on_identical_reports(tmp_path):
    rep = _report(**{"train.s": 1.0})
    base = tmp_path / "base.json"
    fresh = tmp_path / "fresh.json"
    base.write_text(json.dumps(rep))
    fresh.write_text(json.dumps(rep))
    r = _run_cli("--baseline", str(base), "--fresh", str(fresh))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "train.s" in r.stdout


def test_cli_fails_on_injected_slowdown(tmp_path):
    base = tmp_path / "base.json"
    fresh = tmp_path / "fresh.json"
    base.write_text(json.dumps(_report(**{"train.s": 1.0})))
    fresh.write_text(json.dumps(_report(**{"train.s": 2.0})))
    out = tmp_path / "delta"
    r = _run_cli("--baseline", str(base), "--fresh", str(fresh),
                 "--out", str(out))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "regressed" in r.stdout
    # the delta artifact is written even (especially) on failure
    payload = json.loads((tmp_path / "delta.json").read_text())
    rows = [d for sec in payload["sections"] for d in sec["deltas"]]
    assert any(d["status"] == "regressed" for d in rows)
    assert (tmp_path / "delta.md").exists()


def test_cli_respects_tolerance_flag(tmp_path):
    base = tmp_path / "base.json"
    fresh = tmp_path / "fresh.json"
    base.write_text(json.dumps(_report(**{"train.s": 1.0})))
    fresh.write_text(json.dumps(_report(**{"train.s": 2.0})))
    r = _run_cli("--baseline", str(base), "--fresh", str(fresh),
                 "--tolerance", "3.0")
    assert r.returncode == 0, r.stdout + r.stderr


def test_gate_parent_never_initialises_a_jax_backend(tmp_path):
    """The gate starts benchmark children that may need the chip, which
    belongs to one process at a time: the parent may import JAX but must
    never initialise a backend."""
    rep = tmp_path / "rep.json"
    rep.write_text(json.dumps(_report(**{"train.s": 1.0})))
    code = (
        "import runpy, sys\n"
        "from jax._src import xla_bridge\n"
        f"sys.argv = ['gate', '--baseline', {str(rep)!r},"
        f" '--fresh', {str(rep)!r}]\n"
        "try:\n"
        f"    runpy.run_path({str(CHECK)!r}, run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert e.code == 0, e.code\n"
        "print('backend', xla_bridge.backends_are_initialized())\n")
    env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "backend False" in r.stdout, r.stdout
