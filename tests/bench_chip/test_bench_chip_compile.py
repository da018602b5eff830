"""Each cell's timed program compiles for one described TPU v5e chip at
the cell's own sizes. Nothing runs; the topology is described inside a
fixture, after the tests have started."""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.chip import harness
from benchmarks.chip.kinds.common import program_lm
from benchmarks.chip.references import dense_lm

SPEC = harness.load_spec()
#: what one v5e chip lets a program use (its ``bytes_limit``)
HBM = 15.75 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def shapes(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM


@pytest.mark.parametrize("cell", ["mlp_mnist_h200.rel_train",
                                  "mlp_mnist_h200.dense_train"])
def test_mlp_query_compiles(one_chip, cell):
    from repro.core import Engine, nn2sql

    res = harness.resolve(SPEC, cell)
    c, mix = res["config"], res["traffic"]
    graph = nn2sql.build_graph(nn2sql.MLPSpec(
        c["rows"], c["features"], c["hidden"], c["classes"], c["lr"]))
    engine = Engine(mix["engine"])

    def mlp_query(w, x, y):
        return nn2sql.train(graph, w, x, y, mix["iters_per_query"],
                            engine)[0]

    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    w = {"w_xh": f32(c["features"], c["hidden"]),
         "w_ho": f32(c["hidden"], c["classes"])}
    with jax.default_matmul_precision(c["matmul_precision"]):
        fits(jax.jit(mlp_query).lower(
            w, f32(c["rows"], c["features"]),
            f32(c["rows"], c["classes"])).compile())


def granite(cell):
    res = harness.resolve(SPEC, cell)
    cfg = res["config"]
    params = jax.eval_shape(partial(dense_lm.init_params, cfg),
                            jax.random.PRNGKey(0))
    return cfg, res["traffic"], program_lm(cfg), params


def test_train_step_compiles_and_fits(one_chip):
    from repro.optim import adamw
    from repro.train import Trainer

    cfg, mix, lm, params = granite("granite_3_8b_l2.train_4x2048")
    o = mix["optimizer"]
    opt = adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"])
    step = Trainer(lm, opt, None, clip_norm=o["clip_norm"]).step_fn
    tok = jax.ShapeDtypeStruct((mix["batch"], mix["seq_len"]), jnp.int32,
                               sharding=one_chip)
    p = shapes(params, one_chip)
    fits(step.lower(p, shapes(jax.eval_shape(opt.init, params), one_chip),
                    {"tokens": tok, "labels": tok}).compile())

