"""Distributed pieces that need a multi-device mesh: run in subprocesses
with 8 fake CPU devices (keeps the main test process on 1 device)."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(__file__))


def _run(code: str, timeout=900, env_extra=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(env_extra or {})
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, cwd=ROOT, env=env)
    return r


_RING = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.train import ring_allreduce_q8

mesh = make_test_mesh((8,), ("pod",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 1000)) * 2

f = jax.shard_map(lambda s: ring_allreduce_q8(s[0], "pod")[None],
              mesh=mesh, in_specs=P("pod", None), out_specs=P("pod", None))
got = f(x)   # every shard: the int8-wire ring sum
want = x.sum(axis=0)
rel = float(jnp.abs(got[0] - want).max() / jnp.abs(want).max())
assert rel < 0.05, rel
# HLO carries int8 collective-permutes (the wire-compression evidence)
txt = jax.jit(f).lower(x).compile().as_text()
assert "s8[" in txt and "collective-permute" in txt
print("RING_OK rel=%.4f" % rel)
"""


def test_ring_allreduce_q8_correct_and_int8_on_wire():
    r = _run(_RING)
    assert "RING_OK" in r.stdout, (r.stdout, r.stderr[-2000:])


_DRY = r"""
import os
os.environ["REPRO_DRYRUN_DEVICES"] = "8"
os.environ["REPRO_TEST_MESH"] = "%s"
import sys; sys.path.insert(0, "src")
from repro.launch.dryrun import run_cell
import tempfile, json
out = tempfile.mkdtemp()
rec = run_cell("%s", "%s", "%s", out)
assert "skipped" not in rec, rec
assert rec["memory"]["argument_size_in_bytes"] > 0
assert rec["collectives"]["total_wire_bytes"] > 0
assert rec["collectives"]["unknown_trip_conditions"] == 0
print("DRYRUN_OK", rec["arch"], rec["shape"], rec["mesh"],
      int(rec["collectives"]["total_wire_bytes"]))
"""


def test_dryrun_small_mesh_train():
    """The dry-run machinery end-to-end on a tiny mesh: lower + compile +
    memory/cost/collective extraction for a full-size arch x shape cell
    would take minutes; the smallest arch keeps it tractable."""
    r = _run(_DRY % ("2x4", "deepseek_moe_16b", "train_4k", "pod"),
             timeout=3000)
    assert "DRYRUN_OK" in r.stdout, (r.stdout[-500:], r.stderr[-3000:])


def test_dryrun_small_mesh_multipod_decode():
    r = _run(_DRY % ("2x2x2", "deepseek_moe_16b", "decode_32k", "multipod"),
             timeout=3000)
    assert "DRYRUN_OK" in r.stdout, (r.stdout[-500:], r.stderr[-3000:])


_PIPE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.train import pipeline_forward
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((4,), ("pod",))
L, D, B = 8, 16, 8
W = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.2
b = jax.random.normal(jax.random.PRNGKey(1), (L, D)) * 0.1
x = jax.random.normal(jax.random.PRNGKey(2), (B, D))
layer = lambda p, h: jnp.tanh(h @ p["w"] + p["b"])
ref = x
for i in range(L):
    ref = layer({"w": W[i], "b": b[i]}, ref)
out = pipeline_forward(layer, {"w": W, "b": b}, x, mesh=mesh, n_micro=4)
assert float(jnp.abs(out - ref).max()) < 1e-5
txt = jax.jit(lambda p, xx: pipeline_forward(layer, p, xx, mesh=mesh,
              n_micro=4)).lower({"w": W, "b": b}, x).compile().as_text()
assert "collective-permute(" in txt
print("PIPE_OK")
"""


def test_pipeline_parallel_forward_exact():
    """GPipe-style pipeline over the pod axis == sequential layer scan,
    with the DCN hop visible as a collective-permute."""
    r = _run(_PIPE)
    assert "PIPE_OK" in r.stdout, (r.stdout, r.stderr[-2000:])
