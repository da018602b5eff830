"""Sharding rules, validated against AbstractMesh (no devices needed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.base import get_config
from repro.launch import sharding as sh


def mesh(multi=False):
    if multi:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


class FakeKey:
    def __init__(self, key):
        self.key = key


def spec(names, shape, m=None, stacked=False, fsdp=True):
    path = tuple(FakeKey(n) for n in names)
    return sh.param_spec(path, shape, m or mesh(), fsdp=fsdp,
                         stacked=stacked)


class TestParamRules:
    def test_column_parallel_qkv(self):
        s = spec(("layers", "attn", "wq"), (32, 4096, 4096), stacked=True)
        assert s[2] == "model" and s[0] is None       # L axis untouched
        assert s[1] == "data"                         # FSDP dim

    def test_row_parallel_out(self):
        s = spec(("layers", "attn", "wo"), (32, 4096, 4096), stacked=True)
        assert s[1] == "model"

    def test_expert_parallel(self):
        s = spec(("layers", "moe", "wi"), (40, 16, 6144, 10752),
                 stacked=True)
        assert s[1] == "model"                        # experts over model

    def test_vocab_parallel_embed(self):
        s = spec(("embed",), (64000, 4096))
        assert s[0] == "model"

    def test_non_divisible_vocab_not_sharded(self):
        s = spec(("embed",), (49155, 4096))           # granite vocab
        assert s[0] is None and s[1] == "data"        # FSDP still applies

    def test_small_params_replicated(self):
        assert spec(("layers", "norm1", "w"), (32, 4096),
                    stacked=True) == P(None, None)
        assert spec(("layers", "attn", "q_norm", "w"), (32, 128),
                    stacked=True) == P(None, None)

    def test_full_tree_shardings_cover_all_archs(self):
        for aid in ("yi_6b", "deepseek_v2_lite_16b", "dbrx_132b",
                    "rwkv6_7b", "zamba2_2_7b"):
            cfg = get_config(aid)
            from repro.launch.specs import params_specs
            shapes = params_specs(cfg)
            tree = sh.param_shardings(shapes, mesh())
            # every leaf got a NamedSharding and dims divide
            def check(sds, ns):
                pspec = ns.spec
                for dim, axes in zip(sds.shape, tuple(pspec) + (None,) *
                                     (len(sds.shape) - len(pspec))):
                    if axes is None:
                        continue
                    axes = (axes,) if isinstance(axes, str) else axes
                    size = int(np.prod([mesh().shape[a] for a in axes]))
                    assert dim % size == 0, (aid, sds.shape, pspec)
            jax.tree.map(check, shapes, tree)


class TestBatchAndCache:
    def test_batch_sharded_over_dp(self):
        b = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
        tree = sh.batch_shardings(b, mesh(multi=True), 256)
        assert tree["tokens"].spec == P(("pod", "data"), None)

    def test_batch_of_one_replicated(self):
        b = {"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32)}
        tree = sh.batch_shardings(b, mesh(), 1)
        assert tree["tokens"].spec == P()

    @staticmethod
    def _norm(x):
        return x[0] if isinstance(x, tuple) and len(x) == 1 else x

    def test_gqa_cache_heads_not_divisible_uses_seq(self):
        cfg = get_config("qwen2_5_14b")               # kv heads = 8 < 16
        cache = (jax.ShapeDtypeStruct((48, 128, 8, 32768, 128),
                                      jnp.bfloat16),) * 2
        tree = sh.cache_shardings(cache, mesh(), 128, 32768, cfg)
        s = tree[0].spec
        assert self._norm(s[1]) == "data"             # batch over data
        assert self._norm(s[3]) == "model"            # seq picks up model

    def test_long500k_batch1_seq_sharded(self):
        cfg = get_config("zamba2_2_7b")
        cache = (jax.ShapeDtypeStruct((9, 1, 32, 524288, 80),
                                      jnp.bfloat16),)
        tree = sh.cache_shardings(cache, mesh(), 1, 524288, cfg)
        s = tree[0].spec
        assert self._norm(s[2]) == "model"            # 32 kv heads divide
        assert self._norm(s[3]) == "data"             # SP over data

    def test_mla_latent_cache(self):
        cfg = get_config("deepseek_v2_lite_16b")
        cache = (jax.ShapeDtypeStruct((26, 128, 32768, 512), jnp.bfloat16),)
        tree = sh.cache_shardings(cache, mesh(), 128, 32768, cfg)
        s = tree[0].spec
        assert self._norm(s[1]) == "data"
        assert self._norm(s[2]) == "model"
