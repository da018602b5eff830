"""The four-chip training cell (``kinds/lm_train_mesh.py``): its check
catches the lower-precision control and the planted faults at rehearsal
sizes on the CPU, and its per-layer readers compute what they state on
known inputs."""
from __future__ import annotations

import time
import types

import jax.numpy as jnp
import pytest

from benchmarks.chip import collectives, harness, peaks

CELL = "granite_3_8b_l8.train_2x2"
SPEC = harness.load_spec()


def run() -> dict:
    return harness.run(["--workload", CELL, "--seed", "17", "--seconds",
                        "1", "--rehearse"], time.time())


@pytest.fixture(scope="module")
def sound():
    return run()


def caught(sound_run: dict, broken: dict) -> bool:
    """Not correct, and some compared number over both its limit and ten
    times the sound run's reading of it."""
    return not broken["correct"] and any(
        c["value"] > max(c["limit"], 10 * sound_run["check"][n]["value"])
        for n, c in broken["check"].items())


def test_training_at_fp8_fails():
    res = harness.resolve(SPEC, CELL, rehearse=True)
    drv = harness.driver_class(res["traffic"]["kind"])(
        res["config"], res["traffic"], 2**31 + 12)
    ok, _ = harness.judge(drv.calibrate(["control"])["control"],
                          res["limits"])
    assert not ok


@pytest.fixture
def trainer():
    from repro.train import trainer
    return trainer


def test_step_returning_its_state_unchanged(monkeypatch, sound, trainer):
    make = trainer.make_train_step

    def broken(*a, **k):
        step = make(*a, **k)

        def unchanged(params, opt_state, batch):
            return params, opt_state, step(params, opt_state, batch)[2]

        return unchanged

    monkeypatch.setattr(trainer, "make_train_step", broken)
    assert caught(sound, run())


def test_step_on_half_the_batch(monkeypatch, sound, trainer):
    make = trainer.make_train_step

    def broken(loss_fn, *a, **k):
        def half(params, batch):
            return loss_fn(params, {n: v[:v.shape[0] // 2]
                                    for n, v in batch.items()})
        return make(half, *a, **k)

    monkeypatch.setattr(trainer, "make_train_step", broken)
    assert caught(sound, run())


def test_the_multipliers_reach_the_program(monkeypatch, sound):
    """The program built without granite's residual multiplier reads far
    from the reference that has it."""
    from benchmarks.chip.kinds import lm_train_mesh

    build = lm_train_mesh.granite_program

    def without(cfg):
        return build(dict(cfg, residual_multiplier=1.0))

    monkeypatch.setattr(lm_train_mesh, "granite_program", without)
    assert caught(sound, run())


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------

def reader(name):
    return harness.metric_reader(name)


def test_mfu_divides_by_every_chip_of_the_mesh():
    drv = types.SimpleNamespace(facts={"model_flops": 4 * 197e12 * 10,
                                       "window_s": 20.0, "chips": 4})
    ctx = {"driver": drv, "peaks": peaks.peaks("TPU v5 lite")}
    assert reader("mfu.lm_train_2x2").read(ctx) == pytest.approx(50.0)


def test_collective_gb_sums_the_program_counters():
    tracer = types.SimpleNamespace(counters={
        "train.collective_bytes.all-gather": 3e9,
        "train.collective_bytes.all-reduce": 1.5e9,
        "train.collectives.all-gather": 10, "train.host_syncs": 7})
    got = reader("collective_gb.lm_train_2x2").read(
        {"driver": types.SimpleNamespace(tracer=tracer)})
    assert got == pytest.approx(4.5)
    empty = types.SimpleNamespace(tracer=types.SimpleNamespace(counters={}))
    assert reader("collective_gb.lm_train_2x2").read(
        {"driver": empty}) is None


@pytest.mark.parametrize("text,want", [
    ("%all-gather-start.3 = (bf16[8]{0}, bf16[16]{0}) "
     "all-gather-start(%p), channel_id=1", True),
    ("%all-gather-done.3 = bf16[16]{0} all-gather-done(%x)", True),
    ("%all-reduce.9 = f32[1,128]{1,0:T(8,128)} all-reduce(%g), "
     "to_apply=%add", True),
    ("%fusion.28 = f32[32,128]{1,0} fusion(%f), kind=kCustom, "
     "calls=%all-reduce-scatter.clone.clone", True),
    ("%async-collective-done = bf16[1,256]{1,0} fusion(%a), kind=kCustom, "
     "calls=%fused_computation.23", True),
    ("%all-to-all.1 = bf16[4,8]{1,0} all-to-all(%x), dimensions={0}", True),
    ("%collective-permute-start.2 = (f32[8]{0}, f32[8]{0}) "
     "collective-permute-start(%x)", True),
    ("%fusion.30 = (f32[1,128,256]{2,1,0}) fusion(%all-gather.43, %w), "
     "kind=kCustom, calls=%async_collective_fusion.30", False),
    ("%convolution.4 = bf16[8,128]{1,0} convolution(%a, %b)", False),
])
def test_collective_ops_by_their_hlo_text(text, want):
    assert collectives.is_collective(text) is want


def test_collective_time_is_self_time_averaged_over_chips():
    ar = "%all-reduce.1 = f32[8]{0} all-reduce(%x), to_apply=%add"
    dot = "%fusion.2 = f32[8]{0} fusion(%x), kind=kOutput, calls=%f"
    ops = {0: [(0, 10, dot), (10, 14, ar), (20, 30, ar)],
           1: [(0, 10, dot), (10, 12, ar)]}
    # chip 0: 4 + 5 (clipped to the window), chip 1: 2
    assert collectives.collective_ns(ops, 0, 25) == pytest.approx(5.5)


def test_readers_read_nothing_untraced():
    ctx = {"driver": None, "trace": None, "peaks": None, "config": {}}
    for name in ("collective_ms.lm_train_2x2", "collective_gb.lm_train_2x2",
                 "mfu.lm_train_2x2", "idle_share.lm_train",
                 "scope_ms.lm_train.mlp"):
        assert reader(name).read(ctx) is None, name


def test_the_cell_reports_its_metrics():
    res = harness.resolve(SPEC, CELL)
    assert res["cell"]["chips"] == 4
    assert [m["name"] for m in res["end_to_end"]] == [
        "lm_train_tokens_per_s", "setup_s"]
    assert {m["name"] for m in res["per_layer"]} == {
        "mfu.lm_train_2x2", "idle_share.lm_train",
        "collective_ms.lm_train_2x2", "collective_gb.lm_train_2x2",
        "scope_ms.lm_train.attention", "scope_ms.lm_train.mlp",
        "scope_ms.lm_train.head_loss", "scope_ms.lm_train.optimizer",
        "scope_ms.lm_train.unscoped"}
    assert res["traffic"]["mesh"] == [2, 2]
    assert harness.resolve(SPEC, CELL, rehearse=True)["traffic"]["mesh"] \
        == [1, 1]


def test_the_check_tells_bf16_products_from_fp8():
    """At rehearsal size the reference's own fp8 control reads well above
    the program's bfloat16 step on the gradients: the comparison is tight
    enough to tell the two apart."""
    res = harness.resolve(SPEC, CELL, rehearse=True)
    drv = harness.driver_class(res["traffic"]["kind"])(
        res["config"], res["traffic"], 2**31 + 21)
    got = drv.calibrate(["program", "control"])
    assert got["control"]["grad_gap"] > 2 * got["program"]["grad_gap"]
    assert all(jnp.isfinite(v) for r in got.values() for v in r.values())
