"""Compile the Pallas kernels and the paper's MLP training step for one
TPU v5e chip, from a described (not attached) ``v5e:2x2`` topology.

Nothing runs: a compile that passes says the chip's compiler accepts the
program at real widths, not that its results are right or fast. The
topology is described inside a fixture, never at import time, so every
test worker collects the same tests and only the worker given this file
loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """Executables compiled for a described chip cannot be read back
    without one, so keep them out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _spec(sharding):
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


# MNIST's 2000×784 relation, padded to whole 1024-tuple blocks
NNZ = -(-2000 * 784 // 1024) * 1024

# (name, fn, argument shapes) at the widths the paper's workloads and the
# configs use: MNIST's 2000×784 relation against a 256-wide layer
# (the paper's 200 padded to the 128-lane block), granite-3-8b's GQA heads
# and 49155×4096 vocabulary, deepseek-v2-lite's 2048-wide tokens at top-6
# routing, and rwkv6-7b's 64 heads of 64.
KERNELS = [
    ("relational_matmul",
     lambda r, c, v, b: ops.relational_matmul(r, c, v, b, 2000,
                                              use_pallas=True),
     [((NNZ,), jnp.int32), ((NNZ,), jnp.int32), ((NNZ,), jnp.float32),
      ((784, 256), jnp.float32)]),
    ("fused_sigmoid_matmul",
     lambda x, w: ops.fused_sigmoid_matmul(x, w, use_pallas=True),
     [((2048, 896), jnp.float32), ((896, 256), jnp.float32)]),
    ("onehot_embed",
     lambda ids, t: ops.onehot_embed(ids, t, use_pallas=True),
     [((4 * 2048,), jnp.int32), ((49155, 4096), jnp.bfloat16)]),
    ("moe_dispatch",
     lambda x, i, g: ops.moe_dispatch(x, i, g, use_pallas=True),
     [((4096, 2048), jnp.bfloat16), ((6 * 4096,), jnp.int32),
      ((6 * 4096,), jnp.float32)]),
    ("flash_attention",
     lambda q, k, v: ops.flash_attention(q, k, v, use_pallas=True),
     [((1, 32, 2048, 128), jnp.bfloat16), ((1, 8, 2048, 128), jnp.bfloat16),
      ((1, 8, 2048, 128), jnp.bfloat16)]),
    ("rwkv6_scan",
     lambda r, k, v, w, u, s0: ops.rwkv6_scan(r, k, v, w, u, s0,
                                              use_pallas=True),
     [((64, 2048, 64), jnp.float32)] * 4
     + [((64, 64), jnp.float32), ((64, 64, 64), jnp.float32)]),
]


@pytest.mark.parametrize("name,fn,shapes", KERNELS,
                         ids=[k[0] for k in KERNELS])
def test_kernel_compiles_for_v5e(one_chip, name, fn, shapes):
    spec = _spec(one_chip)
    compiled = _compile(fn, *(spec(s, d) for s, d in shapes))
    assert "tpu_custom_call" in compiled.as_text(), name


def test_dense_mlp_train_step_compiles_for_v5e(one_chip):
    """The paper's Algorithm-1 training step at the Fig. 9/10 shapes."""
    from repro.core import nn2sql
    from repro.core.engine import Engine

    spec = nn2sql.MLPSpec(2000, 784, 200, 10)
    g = nn2sql.build_graph(spec)
    s = _spec(one_chip)
    w = {"w_xh": s((784, 200)), "w_ho": s((200, 10))}
    x, y = s((2000, 784)), s((2000, 10))
    compiled = _compile(
        lambda w, x, y: nn2sql.train(g, w, x, y, 5, Engine("dense"))[0],
        w, x, y)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 ** 30


def _relational_mlp_query(one_chip):
    """A 10-iteration training query of the paper's network on the
    relational engine, compiled with no precision context around it."""
    from repro.core import nn2sql
    from repro.core.engine import Engine

    g = nn2sql.build_graph(nn2sql.MLPSpec(2000, 784, 200, 10))
    s = _spec(one_chip)
    w = {"w_xh": s((784, 200)), "w_ho": s((200, 10))}
    return _compile(
        lambda w, x, y: nn2sql.train(g, w, x, y, 10, Engine("relational"))[0],
        w, s((2000, 784)), s((2000, 10)))


def test_relational_mlp_query_groups_without_a_scatter(one_chip):
    """A 10-iteration training query of the paper's network on the
    relational engine: every product's group-by is a reduction over the
    fixed runs of a canonical relation, so the program holds no scatter,
    keeps no joined tuples in memory (1.25 GB for the 2000×784·784×200
    product), and rounds no operand of a product to bfloat16."""
    compiled = _relational_mlp_query(one_chip)
    text = compiled.as_text()
    assert not re.search(r"\bscatter\(", text)
    for op in re.findall(r"\b(?:dot|convolution)\(.*", text):
        assert "operand_precision={highest,highest}" in op
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 26


def test_relational_mlp_query_runs_its_products_on_the_mxu(one_chip):
    """The same 10-iteration relational query holds exactly the dense
    query's five products (two forward, three backward) as MXU ops, each at
    float32 ("highest") with no precision context around the compile."""
    text = _relational_mlp_query(one_chip).as_text()
    products = re.findall(r"= \S+ (?:dot|convolution)\(.*", text)
    assert len(products) == 5
    for op in products:
        assert "operand_precision={highest,highest}" in op


def test_granite_train_step_fits_a_2x2_mesh(topo):
    """Granite-3.0-8B as published (its multipliers and eps), 8 of its 40
    layers, trained through ``Trainer(mesh=...)`` on the four chips of a
    v5e:2x2 at batch 16 × 2048: every chip's plan fits its memory, and
    the step moves data between chips."""
    import dataclasses

    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from repro.configs.base import get_config
    from repro.nn.model import LM
    from repro.optim import adamw
    from repro.obs.hlo import collectives
    from repro.train import Trainer

    cfg = dataclasses.replace(
        get_config("granite_3_8b"), n_layers=8, embedding_multiplier=12.0,
        attention_multiplier=0.0078125, residual_multiplier=0.22,
        logits_scaling=16.0, norm_eps=1e-5)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    opt = adamw(3e-4)
    tr = Trainer(LM(cfg), opt, None, mesh=mesh)
    p = jax.eval_shape(tr.model.init, jax.random.PRNGKey(0))
    place = lambda tree, sh: jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), tree, sh)
    tok = jax.ShapeDtypeStruct((16, 2048), jnp.int32, sharding=NamedSharding(
        mesh, PartitionSpec("data", None)))
    compiled = tr.step_fn.lower(
        place(p, tr.param_sharding),
        place(jax.eval_shape(opt.init, p), tr.opt_sharding),
        {"tokens": tok, "labels": tok}).compile()
    assert compiled.memory_analysis().peak_memory_in_bytes < 15.75 * 2**30
    moved = collectives(compiled.as_text())
    assert moved["all-gather"]["bytes"] > 0 and moved["all-reduce"]["count"]
