"""``Trainer(mesh=...)`` on four virtual CPU devices against
``Trainer(mesh=None)``, its tracing (the ``train.place`` span, the
collective counters), and the launcher's ``--mesh``.

Each case runs in a subprocess of its own with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``: the test process
keeps the one CPU device every other test sees.
"""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: AdamW's learning rate of the compared step
LR = 1e-3
#: the loss of one step on the mesh and on one device: logits in bfloat16
#: (spacing 2^-5 at the loss's scale of 5.5), partial sums added in
#: another order on the mesh; the mean over 128 tokens moves far less
LOSS_TOL = 1e-3
#: relative gap of the gradient norms: bfloat16 partial products summed
#: in another order
GNORM_RTOL = 1e-2
#: AdamW's first step moves each element by lr·(sign(g) + wd·p); where a
#: gradient element is within rounding of zero the two programs may take
#: opposite signs and move it 2·lr apart. Gaussian-spread gradients put
#: about 0.4·ε of elements there, ε the relative noise of a few bfloat16
#: spacings, so under 1%; 5% leaves room for gradients peaked at zero
FLIP_SHARE = 5e-2

STEP_SCRIPT = f"""
import dataclasses, json
import jax, numpy as np
from repro import obs
from repro.configs.base import get_config
from repro.data import TokenPipeline
from repro.launch.mesh import make_mesh
from repro.nn.model import LM
from repro.optim import adamw
from repro.train import Trainer

cfg = dataclasses.replace(
    get_config("granite_3_8b", reduced=True), embedding_multiplier=12.0,
    attention_multiplier=0.0078125, residual_multiplier=0.22,
    logits_scaling=16.0, norm_eps=1e-5)
lm = LM(cfg)
data = TokenPipeline(vocab=cfg.vocab, seq_len=32, global_batch=4)
host = lambda t: jax.tree.map(np.asarray, t)
out = {{}}

mesh_tr = obs.Tracer()
with obs.use(mesh_tr):
    t4 = Trainer(lm, adamw({LR}), data, mesh=make_mesh((2, 2)))
    p, o = t4.init_state(jax.random.PRNGKey(0))
    out["specs"] = {{"embed": str(p["embed"].sharding.spec),
                    "wq": str(p["layers"]["attn"]["wq"].sharding.spec),
                    "m_wq": str(o["m"]["layers"]["attn"]["wq"].sharding.spec)}}
    b = t4.place_batch(data.batch_at(0))
    out["batch_spec"] = str(b["tokens"].sharding.spec)
    p, o, m4 = t4.step_fn(p, o, b)
    p4, m4 = host(p), host(m4)
    out["counters_first"] = dict(mesh_tr.counters)
    p, o, _ = t4.step_fn(p, o, t4.place_batch(data.batch_at(1)))
    out["counters_same_shape"] = dict(mesh_tr.counters)
    half = {{k: v[:2] for k, v in data.batch_at(2).items()}}
    t4.step_fn(p, o, t4.place_batch(half))
    out["counters_new_shape"] = dict(mesh_tr.counters)
out["mesh_spans"] = [s.name for s in mesh_tr.spans]

one_tr = obs.Tracer()
with obs.use(one_tr):
    t1 = Trainer(lm, adamw({LR}), data)
    p, o = t1.init_state(jax.random.PRNGKey(0))
    p, o, m1 = t1.step_fn(p, o, data.batch_at(0))
    p1, m1 = host(p), host(m1)
out["one_counters"] = dict(one_tr.counters)
out["one_spans"] = [s.name for s in one_tr.spans]

d = [np.abs(a.astype(np.float64) - c.astype(np.float64))
     for a, c in zip(jax.tree.leaves(p1), jax.tree.leaves(p4))]
out.update(loss=[float(m4["loss"]), float(m1["loss"])],
           gnorm=[float(m4["grad_norm"]), float(m1["grad_norm"])],
           max_dp=max(float(x.max()) for x in d),
           flips=sum(int((x > {LR}).sum()) for x in d) / sum(x.size for x in d))
print(json.dumps(out))
"""


def run4(code: str, timeout=600) -> dict:
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=timeout,
        env={"PYTHONPATH": f"{ROOT / 'src'}:{ROOT}", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def steps():
    return run4(STEP_SCRIPT)


def test_state_and_batch_take_the_sharding_rules(steps):
    assert steps["specs"] == {
        "embed": "PartitionSpec('model', 'data')",       # vocab × FSDP
        "wq": "PartitionSpec(None, 'data', 'model')",    # FSDP × TP
        "m_wq": "PartitionSpec(None, 'data', 'model')"}  # ZeRO
    assert steps["batch_spec"] == "PartitionSpec('data', None)"


def test_mesh_step_agrees_with_one_device(steps):
    l4, l1 = steps["loss"]
    g4, g1 = steps["gnorm"]
    assert abs(l4 - l1) <= LOSS_TOL, steps["loss"]
    assert abs(g4 - g1) / g1 <= GNORM_RTOL, steps["gnorm"]
    assert steps["max_dp"] <= 2 * LR * (1 + 1e-3), steps["max_dp"]
    assert steps["flips"] <= FLIP_SHARE, steps["flips"]


def test_collectives_are_counted_once_per_compile(steps):
    first = steps["counters_first"]
    kinds = ("all-gather", "reduce-scatter", "all-reduce",
             "collective-permute", "all-to-all")
    assert {f"train.{c}.{k}" for c in ("collectives", "collective_bytes")
            for k in kinds} == set(first)
    assert sum(v for k, v in first.items()
               if k.startswith("train.collectives.")) > 0
    assert sum(v for k, v in first.items()
               if k.startswith("train.collective_bytes.")) > 0
    # a second call of the same signature compiles nothing and adds nothing
    assert steps["counters_same_shape"] == first
    # a batch of another shape compiles again and adds its own program's
    new = steps["counters_new_shape"]
    assert all(new[k] >= first[k] for k in first)
    assert sum(new.values()) > sum(first.values())


def test_place_span_once_with_a_mesh_and_no_collectives_without(steps):
    assert steps["mesh_spans"].count("train.place") == 1
    assert "train.place" not in steps["one_spans"]
    assert not any(k.startswith("train.collective")
                   for k in steps["one_counters"])


def test_launcher_trains_on_a_mesh():
    code = ("import sys\n"
            "sys.argv = ['train', '--arch', 'granite_3_8b', '--reduced',"
            " '--steps', '2', '--seq', '16', '--batch', '4',"
            " '--mesh', '2x2']\n"
            "from repro.launch import train\n"
            "from repro.train import trainer\n"
            "made = []\n"
            "init = trainer.Trainer.__init__\n"
            "def spy(self, *a, **k):\n"
            "    init(self, *a, **k)\n"
            "    made.append(self)\n"
            "trainer.Trainer.__init__ = spy\n"
            "train.main()\n"
            "import json\n"
            "print(json.dumps({'mesh': dict(made[0].mesh.shape)}))\n")
    out = run4(code)
    assert out == {"mesh": {"data": 2, "model": 2}}


def test_the_new_cell_rehearses_on_a_2x2_mesh():
    """The four-chip cell's harness run at rehearsal sizes, on a 2x2 mesh
    of virtual devices (its rehearsal mix holds a 1x1 mesh, which the
    in-process rehearsal of every cell runs)."""
    code = ("import json, time\n"
            "from benchmarks.chip import harness\n"
            "resolve = harness.resolve\n"
            "def on_2x2(*a, **k):\n"
            "    res = resolve(*a, **k)\n"
            "    res['traffic']['mesh'] = [2, 2]\n"
            "    return res\n"
            "harness.resolve = on_2x2\n"
            "out = harness.run(['--workload', 'granite_3_8b_l8.train_2x2',"
            " '--seed', str(2**31 + 5), '--seconds', '1', '--rehearse'],"
            " time.time())\n"
            "print(json.dumps(out))\n")
    out = run4(code)
    assert out["rehearsal"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    for name, c in out["check"].items():
        assert c["value"] is not None and c["value"] < 0.1, (name, c)
