"""Device time charged to the program's named scopes (``scopes.py``): the
wire-format reader, the charging rules on events with known answers, the
readers, and two traces recorded on one TPU v5e, one from before the
program had scopes and one with them (``record_scopes_fixture.py``)."""
from __future__ import annotations

import pathlib
import types

import pytest

from benchmarks.chip import harness, scopes, trace

DATA = pathlib.Path(__file__).parent / "data"
UNSCOPED_FIXTURE = DATA / "mlp_dense_v5e.xplane.pb.gz"
SCOPED_FIXTURE = DATA / "scopes_v5e.xplane.pb.gz"
SPEC = harness.load_spec()
SCOPE_METRICS = [m for m in SPEC["per_layer"]
                 if m["name"].startswith("scope_ms.")]


# ---------------------------------------------------------------------------
# the wire-format reader
# ---------------------------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        byte, n = n & 0x7F, n >> 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*pairs) -> bytes:
    return b"".join(_field(n, v) for n, v in pairs)


def synthetic_space() -> bytes:
    """One TPU plane: op A's ``tf_op`` a string, op B's a reference, op C
    with no ``tf_op``; a host plane whose metadata must be ignored."""
    stat_md = [(1, "flops"), (2, "tf_op"), (3, "jit(f)/lm.mlp/ref_path:")]
    stats = [_field(5, _msg((1, i), (2, _msg((1, i), (2, n)))))
             for i, n in stat_md]
    events = [
        (10, "%a = f32[2] add()", [_msg((1, 1), (3, 7)),
                                   _msg((1, 2), (5, "jit(f)/ir.MatMul/dot:"))]),
        (11, "%b = f32[2] mul()", [_msg((1, 2), (7, 3))]),
        (12, "%c = f32[2] copy()", [_msg((1, 1), (3, 0))]),
    ]
    ev_fields = [_field(4, _msg((1, k), (2, _msg(
        (1, k), (2, text), *[(5, s) for s in st])))) for k, text, st in events]
    device = _msg((1, 5), (2, "/device:TPU:0"), (3, _msg((1, 1), (2, "x")))) \
        + b"".join(ev_fields) + b"".join(stats)
    host = _msg((2, "/host:CPU")) + _field(4, _msg((1, 1), (2, _msg(
        (1, 1), (2, "%a = f32[2] add()")))))
    return _msg((1, device), (1, host), (4, "hostname"))


def test_wire_reader_takes_string_and_reference_stats():
    got = scopes.tf_ops(synthetic_space())
    assert got == {0: {"%a = f32[2] add()": {"jit(f)/ir.MatMul/dot:"},
                       "%b = f32[2] mul()": {"jit(f)/lm.mlp/ref_path:"},
                       "%c = f32[2] copy()": set()}}


def test_wire_reader_maps_the_recorded_fusion_to_its_path():
    ops, _, paths = scopes.load(str(UNSCOPED_FIXTURE))
    texts = [t for t in paths[0] if t.startswith("%fusion.54 ")]
    assert len(texts) == 1
    assert paths[0][texts[0]] == {
        "jit(mlp_query)/while/body/closed_call/jit(step)/jit(fn)/"
        "dot_general:"}
    # every op ProfileData gives has its metadata under the same text
    assert {t for _, _, t in ops[0]} <= set(paths[0])


# ---------------------------------------------------------------------------
# charging
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path, scope", [
    ("jit(step)/lm.layers/while/body/lm.attention/dot_general:",
     "lm.attention"),
    ("jit(step)/transpose(jvp(lm.layers))/while/body/add:", "lm.layers"),
    ("jit(step)/transpose(jvp(lm.layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/lm.attention/dot_general:", "lm.attention"),
    ("jit(q)/while/body/closed_call/jit(step)/jvp(ir.MatMul)/rel.group_by/"
     "scatter-add:", "rel.group_by"),
    ("jit(step)/train.optimizer/mul", "train.optimizer"),
    ("jit(mlp_query)/while/body/closed_call/jit(step)/jit(fn)/dot_general:",
     None),
    ("jit(f)/xlm.attention/lm.attentions2/dot:", None),
    ("", None),
])
def test_innermost_whole_component_scope(path, scope):
    assert scopes.scope_of(path) == scope


def test_an_op_text_with_differing_scopes_is_unscoped():
    assert scopes.label({"jit(a)/lm.mlp/dot:", "jit(b)/lm.mlp/dot:"}) \
        == "lm.mlp"
    assert scopes.label({"jit(a)/lm.mlp/dot:", "jit(b)/lm.head/dot:"}) \
        == scopes.UNSCOPED
    assert scopes.label({"jit(a)/lm.mlp/dot:", "jit(b)/dot:"}) \
        == scopes.UNSCOPED
    assert scopes.label(set()) == scopes.UNSCOPED


def synthetic_ops():
    # window 0..100: a while (0-60) with two body ops, one op whose text
    # two programs share under different scopes, one op without metadata
    ops = {0: [(0, 60, "%while"), (10, 30, "%mm"), (35, 50, "%tr"),
               (60, 70, "%shared"), (70, 80, "%bare"), (90, 120, "%mm")]}
    paths = {0: {"%while": {"jit(q)/while"},
                 "%mm": {"jit(q)/while/body/jvp(ir.MatMul)/rel.join/mul"},
                 "%tr": {"jit(q)/while/body/ir.Transpose/sort"},
                 "%shared": {"jit(a)/lm.mlp/dot", "jit(b)/lm.head/dot"}}}
    return ops, paths


def test_charging_by_hand():
    ops, paths = synthetic_ops()
    got = scopes.charge(ops, paths, 0, 100)
    # the while keeps 60 - 20 - 15 = 25 of its own; %mm 20 + 10 clipped
    assert got == {scopes.UNSCOPED: 25 + 10 + 10, "rel.join": 30,
                   "ir.Transpose": 15}


def test_buckets_sum_to_the_busy_time():
    ops, paths = synthetic_ops()
    ev = trace.Events(ops=ops, spans=[(0, 100, trace.WINDOW_SPAN)])
    busy = trace.reduce(ev, 0, 100)["busy_s"]
    assert sum(scopes.charge(ops, paths, 0, 100).values()) * 1e-9 \
        == pytest.approx(busy)


def test_devices_are_averaged_as_busy_time_is():
    ops = {0: [(0, 40, "%x")], 1: [(0, 20, "%x")]}
    paths = {0: {"%x": {"f/lm.mlp/dot"}}, 1: {"%x": {"f/lm.mlp/dot"}}}
    assert scopes.charge(ops, paths, 0, 100) == {"lm.mlp": 30}


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def fake_driver(attempted=5, failed=1, iters=10):
    return types.SimpleNamespace(attempted=attempted, failed=failed,
                                 iters=iters)


def test_every_scope_metric_has_its_own_reader():
    assert len(SCOPE_METRICS) == 11
    for m in SCOPE_METRICS:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").exists()
        assert m["unit"] in ("ms/iteration", "ms/step")
        assert m["source"] == "device_trace"


@pytest.mark.parametrize("name", [m["name"] for m in SCOPE_METRICS])
def test_reader_is_none_untraced(name):
    ctx = {"driver": fake_driver(), "trace": None, "peaks": None,
           "config": {}}
    assert harness.metric_reader(name).read(ctx) is None


@pytest.mark.parametrize("name", [m["name"] for m in SCOPE_METRICS])
def test_reader_is_none_on_a_program_without_scopes(name, monkeypatch):
    monkeypatch.setattr(scopes, "run_trace", lambda: str(UNSCOPED_FIXTURE))
    ctx = {"driver": fake_driver(), "trace": {"busy_s": 1.0}}
    assert harness.metric_reader(name).read(ctx) is None


def test_reader_divides_by_the_work_completed(monkeypatch):
    monkeypatch.setattr(scopes, "run_trace", lambda: "x.xplane.pb")
    monkeypatch.setattr(scopes, "charges", lambda path: {
        "ir.MatMul": 4e6, "rel.join": 2e6, "rel.group_by": 2e6,
        scopes.UNSCOPED: 1e6})
    ctx = {"driver": fake_driver(attempted=5, failed=1, iters=10),
           "trace": {"busy_s": 1.0}}
    read = lambda n: harness.metric_reader(n).read(ctx)
    # 8 ms over (5 - 1) queries x 10 iterations; 1 ms over 4 steps
    assert read("scope_ms.mlp_train.matmul") == pytest.approx(0.2)
    assert read("scope_ms.lm_train.unscoped") == pytest.approx(0.25)
    ctx["driver"] = fake_driver(attempted=3, failed=3)
    assert read("scope_ms.mlp_train.matmul") is None


# ---------------------------------------------------------------------------
# the recorded trace of the scoped program
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    ops, (lo, hi), paths = scopes.load(str(SCOPED_FIXTURE))
    return ops, lo, hi, paths, scopes.charge(ops, paths, lo, hi)


def test_recorded_buckets_sum_to_busy_time(recorded):
    ops, lo, hi, _, got = recorded
    busy = trace.reduce(trace.Events(ops=ops, spans=[]), lo, hi)["busy_s"]
    assert sum(got.values()) * 1e-9 == pytest.approx(busy, rel=1e-9)


def loop_state(paths) -> bool:
    """XLA gives the fusion that carries a loop's state from one iteration
    to the next the ``while`` op's own metadata (``jit(mlp_query)/while:``),
    so it lies under no program scope. In the MLP query it copies the
    784×200 weights, a share of busy time that grows as the rows shrink."""
    return bool(paths) and all(
        p.rsplit(":", 1)[0].endswith("/while") for p in paths)


def test_recorded_program_scopes_take_nine_tenths(recorded):
    ops, lo, hi, paths, got = recorded
    total = sum(got.values())
    (dev, dev_ops), = ops.items()
    named = [(a, b, "loop state" if loop_state(paths[dev].get(t, ()))
              else scopes.label(paths[dev].get(t, ()))) for a, b, t in dev_ops]
    loop = trace.self_times(named, lo, hi).get("loop state", 0.0)
    unscoped = got.get(scopes.UNSCOPED, 0.0)
    # the loop-state fusion is the one named exception
    assert 0 < loop <= unscoped
    assert 1 - (unscoped - loop) / total >= 0.9
    for scope in ("ir.MatMul", "ir.Transpose", "ir.Update", "rel.join",
                  "rel.group_by", "rel.pivot", "lm.attention", "lm.mlp",
                  "lm.head", "lm.loss", "train.optimizer"):
        assert got.get(scope, 0.0) > 0, scope


def test_recorded_charges_are_read_once(recorded):
    first = scopes.charges(str(SCOPED_FIXTURE))
    assert scopes.charges(str(SCOPED_FIXTURE)) is first
    assert first == pytest.approx(recorded[-1])
