"""Compile the Pallas kernels and the paper's MLP training step for one
TPU v5e chip, from a described (not attached) ``v5e:2x2`` topology.

Nothing runs: a compile that passes says the chip's compiler accepts the
program at real widths, not that its results are right or fast. The
topology is described inside a fixture, never at import time, so every
test worker collects the same tests and only the worker given this file
loads the TPU library.
"""
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
