"""The program's layers on the profiler's clock: named scopes in the
compiled HLO of both JAX engines and of the LM train step, ``obs`` spans as
profiler annotations on the host plane, ``Trainer.run``'s step annotations
and spans, and the host-sync counters of training and serving."""
from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.configs.base import get_config
from repro.core import Engine, nn2sql
from repro.core import expr as E
from repro.core.autodiff import ReduceDeriv
from repro.data import TokenPipeline
from repro.db.plan_cache import PlanCache
from repro.db.sql_engine import SQLEngine
from repro.nn.model import LM
from repro.optim import adamw
from repro.serving import Request, ServingEngine
from repro.train import Trainer, make_train_step

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCOPE = re.compile(r"(?:ir|rel|lm|train)\.[A-Za-z_]+")


def scopes_in(compiled_text: str) -> set[str]:
    """Every program scope among the ``op_name`` path components."""
    names = re.findall(r'op_name="([^"]*)"', compiled_text)
    return {s for n in names for s in SCOPE.findall(n)}


def graph_kinds(roots) -> set[str]:
    seen, stack, out = set(), list(roots), set()
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        if not isinstance(n, E.Var):
            out.add(f"ir.{type(n).__name__}")
        stack.extend(n.children())
    return out


# ---------------------------------------------------------------------------
# named scopes in the compiled HLO
# ---------------------------------------------------------------------------

GRAPH = nn2sql.build_graph(nn2sql.MLPSpec(16, 8, 4, 3, lr=0.01))


def mlp_query_hlo(kind: str, lowered: bool = False) -> str:
    eng = Engine(kind)
    query = jax.jit(lambda w, x, y: nn2sql.train(GRAPH, w, x, y, 2, eng)[0])
    w = {"w_xh": jnp.ones((8, 4)), "w_ho": jnp.ones((4, 3))}
    low = query.lower(w, jnp.ones((16, 8)), jnp.ones((16, 3)))
    return low.as_text(debug_info=True) if lowered else \
        low.compile().as_text()


@pytest.mark.parametrize("kind", ["dense", "relational"])
def test_mlp_query_carries_a_scope_per_ir_node(kind):
    from repro.core import autodiff

    grads = autodiff.gradients(GRAPH.loss, [GRAPH.w_xh, GRAPH.w_ho])
    kinds = graph_kinds([GRAPH.loss, *grads.values()]) | {"ir.Update"}
    assert {"ir.MatMul", "ir.Transpose", "ir.Map", "ir.MapDeriv",
            "ir.Hadamard", "ir.Sub"} <= kinds
    rel = {"rel.join", "rel.group_by", "rel.pivot"}
    lowered = set(SCOPE.findall(mlp_query_hlo(kind, lowered=True)))
    compiled = scopes_in(mlp_query_hlo(kind))
    want = kinds | (rel if kind == "relational" else set())
    assert want <= lowered
    # XLA folds the transposes into the products (each engine's product
    # is a contraction) and the constants away; every other scope reaches
    # the compiled program
    assert want - {"ir.Transpose", "ir.Const"} <= compiled
    if kind == "dense":
        assert not rel & compiled


def test_zoo_nodes_carry_their_kind():
    from repro.core import autodiff

    x = E.var("x", (4, 6))
    red = E.row_reduce(x, "max", 1)
    grad = autodiff.gradients(E.row_reduce(red, "sum", 0), [x])[x]
    roots = [E.softmax(x), red, grad]
    assert any(isinstance(n, ReduceDeriv) for n in E.topo_order(grad))
    for kind in ("dense", "relational"):
        fn = Engine(kind).eval_fn(roots)
        low = fn.lower({"x": jnp.ones((4, 6))}).as_text(debug_info=True)
        assert {"ir.Softmax", "ir.RowReduce", "ir.ReduceDeriv"} <= set(
            SCOPE.findall(low)), kind


def test_lm_train_step_carries_the_model_and_trainer_scopes():
    cfg = get_config("granite_3_8b", reduced=True)
    lm = LM(cfg)
    opt = adamw(1e-3)
    step = jax.jit(make_train_step(lm.loss_fn, opt, 1, 1.0))
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(opt.init, params)
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = step.lower(params, state, {"tokens": tok, "labels": tok}
                      ).compile().as_text()
    assert {"lm.embed", "lm.layers", "lm.attention", "lm.mlp", "lm.head",
            "lm.loss", "train.clip", "train.optimizer"} <= scopes_in(text)


def test_chunked_loss_and_moe_carry_head_loss_and_mlp():
    import dataclasses

    cfg = dataclasses.replace(get_config("granite_3_8b", reduced=True),
                              loss_impl="chunked", loss_chunk=100)
    lm = LM(cfg)
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = jax.jit(lm.loss_fn).lower(
        params, {"tokens": tok, "labels": tok}).compile().as_text()
    assert {"lm.head", "lm.loss"} <= scopes_in(text)
    moe = LM(get_config("deepseek_v2_lite_16b", reduced=True))
    mp = jax.eval_shape(moe.init, jax.random.PRNGKey(0))
    text = jax.jit(moe.loss_fn).lower(
        mp, {"tokens": tok, "labels": tok}).compile().as_text()
    assert {"lm.attention", "lm.mlp", "lm.layers"} <= scopes_in(text)


@pytest.mark.parametrize("how", ["enable", "import_with_env",
                                 "import_without_env"])
def test_compile_cache_keys_keep_the_scopes(how, tmp_path):
    """An executable cached from the same computation without its scopes
    is never served: ``enable_compile_cache`` keys the persistent cache
    with the metadata, source paths cut at ``repro/`` so a moved checkout
    still hits, and so does ``import repro`` where the environment names a
    cache directory. Without one, importing the package sets nothing."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(ROOT / "src")
    if how == "import_with_env":
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = ("import json, sys\n"
            "import repro\n"
            "imported = 'jax' in sys.modules\n"
            "import jax\n"
            + ("from repro.launch.compile_cache import enable_compile_cache\n"
               "enable_compile_cache()\n" if how == "enable" else "")
            + "c = jax.config\n"
            "print(json.dumps([imported,"
            " c.jax_compilation_cache_include_metadata_in_key,"
            " c.jax_hlo_source_file_canonicalization_regex]))\n")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-3000:]
    imported_jax, keyed, pattern = json.loads(r.stdout)
    if how == "import_without_env":
        assert (imported_jax, keyed, pattern) == (False, False, None)
        return
    assert keyed
    for root in ("/a/b/src", "/elsewhere/x/src"):
        assert re.sub(pattern, "", f"{root}/repro/core/dense.py") \
            == "repro/core/dense.py"
    assert re.sub(pattern, "", "/c/benchmarks/chip/kinds/lm_train.py") \
        == "benchmarks/chip/kinds/lm_train.py"


# ---------------------------------------------------------------------------
# obs spans on the profiler's host plane
# ---------------------------------------------------------------------------

def host_events(logdir) -> list[tuple[str, float, float, str]]:
    """(name, start, end, line) of every host-plane event."""
    found = sorted(pathlib.Path(logdir).glob("**/*.xplane.pb"))
    assert len(found) == 1
    out = []
    for plane in ProfileData.from_file(str(found[0])).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         line.name) for ev in line.events]
    return out


def inside(inner, outer) -> bool:
    return (inner[3] == outer[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = get_config("yi_6b", reduced=True)
    lm = LM(cfg)
    return lm, lm.init(jax.random.PRNGKey(0))


def serve_and_query(tracer, tiny_lm):
    """A served request, an SQL evaluate and an abandoned span, with
    ``tracer`` installed."""
    lm, params = tiny_lm
    eng = ServingEngine(lm, params, max_len=16, batch_slots=1)
    a = E.var("a", (3, 4))
    b = E.var("b", (4, 2))
    root = E.matmul(a, b, name="c")
    env = {"a": np.arange(12.0).reshape(3, 4), "b": np.ones((4, 2))}
    with SQLEngine(plan_cache_=PlanCache(path=None)) as sql, \
            obs.use(tracer):
        eng.submit(Request(0, np.array([3, 1, 4], np.int32),
                           max_new_tokens=2))
        eng.run_to_completion()
        sql.evaluate([root], env)
        with tracer.span("outer"):
            left_open = tracer.span("abandoned")
            left_open.__enter__()


def test_collecting_spans_land_nested_on_the_host_plane(tmp_path,
                                                        tiny_lm):
    serve_and_query(obs.Tracer(), tiny_lm)        # compile outside
    tracer = obs.Tracer()
    with jax.profiler.trace(str(tmp_path)):
        serve_and_query(tracer, tiny_lm)
    evs = host_events(tmp_path)
    by = {}
    for e in evs:
        by.setdefault(e[0], []).append(e)
    for name in ("serve.step", "serve.prefill", "sql.evaluate", "outer",
                 "abandoned"):
        assert name in by, name
    assert any(inside(p, s) for p in by["serve.prefill"]
               for s in by["serve.step"])
    assert inside(by["abandoned"][0], by["outer"][0])
    # the abandoned span ends on the tracer and on the profiler alike
    ab = next(s for s in tracer.spans if s.name == "abandoned")
    assert ab.attrs["abandoned"] and ab._note is not None
    names = {s.name for s in tracer.spans}
    assert {"serve.step", "sql.evaluate"} <= names


def test_null_tracer_spans_do_not_reach_the_profiler(tmp_path, tiny_lm):
    serve_and_query(obs.NullTracer(), tiny_lm)
    with jax.profiler.trace(str(tmp_path)):
        serve_and_query(obs.NullTracer(), tiny_lm)
    names = {e[0] for e in host_events(tmp_path)}
    assert not names & {"serve.step", "serve.prefill", "sql.evaluate",
                        "outer", "abandoned"}


def test_spans_outside_a_capture_hold_no_annotation():
    tracer = obs.Tracer()
    with tracer.span("x"):
        pass
    assert tracer.spans[0]._note is None


# ---------------------------------------------------------------------------
# Trainer.run and the host-sync counters
# ---------------------------------------------------------------------------

def test_trainer_run_annotates_each_step_and_counts_syncs(tmp_path,
                                                          tiny_lm):
    lm, _ = tiny_lm
    data = TokenPipeline(vocab=lm.cfg.vocab, seq_len=8, global_batch=2)
    Trainer(lm, adamw(1e-3), data).run(jax.random.PRNGKey(0), 1,
                                       log_every=0)     # compile outside
    trainer = Trainer(lm, adamw(1e-3), data,
                      checkpoint_dir=str(tmp_path / "ckpt"),
                      checkpoint_every=2)
    tracer = obs.Tracer()
    steps = 3
    with jax.profiler.trace(str(tmp_path / "trace")), obs.use(tracer):
        trainer.run(jax.random.PRNGKey(0), steps, log_every=0)
    evs = host_events(tmp_path / "trace")
    step_marks = [e for e in evs if e[0] == "train"]
    assert len(step_marks) == steps
    got = [s.name for s in tracer.spans]
    for name in ("train.batch", "train.step", "train.read_metrics"):
        assert got.count(name) == steps, name
    assert got.count("train.checkpoint") == 2      # step 2 and the last
    # one read of the metrics per step, one snapshot per checkpoint
    assert tracer.counters["train.host_syncs"] == steps + 2
    for name in ("train.step", "train.read_metrics"):
        marks = [e for e in evs if e[0] == name]
        assert len(marks) == steps
        assert all(any(inside(m, s) for s in step_marks) for m in marks)


def test_serving_counts_a_host_sync_per_sampled_token(tiny_lm):
    lm, params = tiny_lm
    eng = ServingEngine(lm, params, max_len=32, batch_slots=2)
    reqs = [Request(uid, np.arange(1 + uid, dtype=np.int32) + 1,
                    max_new_tokens=2 + uid) for uid in range(3)]
    for r in reqs:
        eng.submit(r)
    tracer = obs.Tracer()
    with obs.use(tracer):
        eng.run_to_completion()
    c = tracer.counters
    assert c["serve.host_syncs"] == sum(len(r.generated) for r in reqs)
    assert c["serve.host_syncs"] == c["serve.decode_tokens"] + c[
        "serve.admitted"]
