"""The lower-precision control, put in the program's place, is not
correct under each cell's own limits: three-pass bfloat16 products for
the float32 MLP, fp8 products for the bfloat16 LM. The control runs on
the CPU here at the largest sizes a test can hold; on the chip it was run
at the cells' own sizes (PERF.md)."""
from __future__ import annotations

from benchmarks.chip import harness, readings

SPEC = harness.load_spec()


def control(cell: str, seed: int, config=None, mix=None,
            rehearse=False) -> bool:
    res = harness.resolve(SPEC, cell, rehearse=rehearse)
    cfg = dict(res["config"], **(config or {}))
    mix = dict(res["traffic"], **(mix or {}))
    kind = mix["kind"]
    drv = harness.driver_class(kind)(cfg, mix, seed)
    got = readings.readings(drv, kind, "control")
    ok, _ = harness.judge(got, res["limits"])
    return ok


def test_mlp_at_three_pass_bf16_fails_at_the_cells_size():
    # the control replaces the program, so it is the same for both MLP
    # cells; the dense cell's program is the cheap one to run beside it
    assert not control("mlp_mnist_h200.dense_train", seed=2**31 + 11)


def test_lm_training_at_fp8_fails():
    assert not control("granite_3_8b_l2.train_4x2048", seed=2**31 + 12,
                       rehearse=True)

