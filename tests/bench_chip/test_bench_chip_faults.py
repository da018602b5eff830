"""A run with its timed path broken underneath comes out not correct:
for each cell, each fault it can have. The harness runs on the CPU at the
rehearsal sizes with the system's code patched, against the cells' own
limits; each fault must also read well above what the sound run reads at
the same size, so that it is the fault that is caught."""
from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from benchmarks.chip import harness


def run(cell: str) -> dict:
    return harness.run(["--workload", cell, "--seed", "17", "--seconds",
                        "1", "--rehearse"], time.time())


@pytest.fixture(scope="module")
def sound():
    cache = {}

    def get(cell):
        if cell not in cache:
            cache[cell] = run(cell)
        return cache[cell]

    return get


def caught(sound_run: dict, broken: dict) -> bool:
    """Not correct, and some compared number over both its limit and ten
    times the sound run's reading of it."""
    return not broken["correct"] and any(
        c["value"] > max(c["limit"], 10 * sound_run["check"][n]["value"])
        for n, c in broken["check"].items())


def first_half_twice(x):
    h = x.shape[0] // 2
    return jnp.concatenate([x[:h], x[:h]])


MLP = ["mlp_mnist_h200.rel_train", "mlp_mnist_h200.dense_train"]


@pytest.fixture
def nn2sql():
    from repro.core import nn2sql
    return nn2sql


@pytest.mark.parametrize("cell", MLP)
def test_mlp_query_returning_its_weights_unchanged(monkeypatch, sound,
                                                   nn2sql, cell):
    sound_run = sound(cell)
    monkeypatch.setattr(nn2sql, "train", lambda g, w, *a, **k: (w, None))
    assert caught(sound_run, run(cell))


@pytest.mark.parametrize("cell", MLP)
def test_mlp_query_leaving_the_hidden_layer_unmoved(monkeypatch, sound,
                                                    nn2sql, cell):
    # the hidden layer left where the query found it: Eq. 11's update
    # lost, while the output layer's goes on
    sound_run, train = sound(cell), nn2sql.train

    def frozen(g, w, *a, **k):
        new, rest = train(g, w, *a, **k)
        return dict(new, w_xh=w["w_xh"]), rest

    monkeypatch.setattr(nn2sql, "train", frozen)
    assert caught(sound_run, run(cell))


@pytest.mark.parametrize("cell", MLP)
def test_mlp_query_on_half_the_batch(monkeypatch, sound, nn2sql, cell):
    # the loss is a sum over rows: the first half counted twice is the
    # mean over that half, scaled to the batch
    sound_run, train = sound(cell), nn2sql.train
    monkeypatch.setattr(nn2sql, "train", lambda g, w, x, y, *a, **k: train(
        g, w, first_half_twice(x), first_half_twice(y), *a, **k))
    assert caught(sound_run, run(cell))


TRAIN = "granite_3_8b_l2.train_4x2048"


@pytest.fixture
def trainer():
    from repro.train import trainer
    return trainer


def test_lm_step_returning_its_state_unchanged(monkeypatch, sound,
                                               trainer):
    sound_run, make = sound(TRAIN), trainer.make_train_step

    def broken(*a, **k):
        step = make(*a, **k)

        def unchanged(params, opt_state, batch):
            return params, opt_state, step(params, opt_state, batch)[2]

        return unchanged

    monkeypatch.setattr(trainer, "make_train_step", broken)
    assert caught(sound_run, run(TRAIN))


def test_lm_step_on_half_the_batch(monkeypatch, sound, trainer):
    sound_run, make = sound(TRAIN), trainer.make_train_step

    def broken(loss_fn, *a, **k):
        def half(params, batch):
            return loss_fn(params, {n: v[:v.shape[0] // 2]
                                    for n, v in batch.items()})
        return make(half, *a, **k)

    monkeypatch.setattr(trainer, "make_train_step", broken)
    assert caught(sound_run, run(TRAIN))

