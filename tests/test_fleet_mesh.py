"""Fleet tier under REAL multi-device placement.

Subprocess-isolated like ``tests/test_sharding.py``: XLA only honors
``--xla_force_host_platform_device_count`` if it lands in ``XLA_FLAGS``
before jax initializes, and the parent test process has long since
initialized jax on a single device.  Deliberately NOT in the ``fast``
subset -- it pays a full jax start + quantize per run.

The property under test is the tentpole acceptance one, on disjoint
per-shard device groups instead of the co-located default: kill 1 of 2
shards mid-flight and every stream (migrated, replayed, undisturbed)
completes bit-identical to ``decode_single``.
"""
import os
import subprocess
import sys

import pytest

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import jax
import numpy as np
from repro.configs.registry import SMOKE_CONFIGS
from repro.launch import engine as E
from repro.launch import fleet as F
from repro.models import lstm_lm, model_zoo
from repro.runtime import sharding as shlib

assert len(jax.devices()) == 4
cfg = SMOKE_CONFIGS["lstm-rnnt"]
bundle = model_zoo.build(cfg)
params, _ = bundle.init(jax.random.PRNGKey(0))
calib = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0, cfg.vocab_size)
qlayers = lstm_lm.quantize_stack(params, cfg, calib)

meshes = shlib.fleet_meshes(2)
assert all(m is not None for m in meshes)
got = [tuple(d.id for d in np.ravel(m.devices)) for m in meshes]
assert got == [(0, 1), (2, 3)], got  # disjoint contiguous groups

rng = np.random.default_rng(7)
reqs = [E.Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=(p,)),
                  max_new_tokens=g)
        for i, (p, g) in enumerate([(2, 8), (3, 7), (5, 6), (2, 9)])]
inj = F.FaultInjector(kills=[dict(shard=0, at_step=5)])
router = F.FleetRouter(params, qlayers, cfg, n_shards=2, slots_per_shard=2,
                       oversubscribe=2.0, policy="srf", injector=inj,
                       meshes=meshes)
for sh, want in zip(router.shards, got):  # weights live on the shard's mesh
    on = {d.id for leaf in jax.tree_util.tree_leaves(sh.engine.weights)
          for d in leaf.devices()}
    assert on == set(want), (on, want)
router.warmup()
router.submit_all(reqs)
results, stats = router.run()
assert stats.kills == 1 and stats.completed == len(reqs)
for r in reqs:
    ref = E.decode_single(params, qlayers, cfg, r.prompt, r.max_new_tokens)
    assert results[r.rid].tokens == ref, f"stream {r.rid} drifted"
print("MESH-FLEET-OK")
"""


@pytest.mark.skipif(os.environ.get("REPRO_SKIP_SUBPROCESS") == "1",
                    reason="subprocess tests disabled")
def test_fleet_on_disjoint_meshes_subprocess():
    """2 shards on disjoint 2-device meshes (forced host CPU devices),
    shard 0 hard-killed mid-flight: recovery across REAL device groups
    stays bit-exact."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT], env=env, cwd=os.getcwd(),
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MESH-FLEET-OK" in out.stdout
