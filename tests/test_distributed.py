"""Distributed operators under a real multi-device mesh.

Runs in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest process keeps its single CPU device (per the assignment:
only the dry-run may see many devices).
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os, json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import hashing, distributed
from collections import defaultdict

mesh = jax.make_mesh(
    (4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2
)
sh = NamedSharding(mesh, P(("data", "model")))
rng = np.random.default_rng(0)
out = {}

# --- shuffle-dedup vs exact oracle, two batches
vals = rng.integers(0, 3000, size=16384).astype(np.int32)
hi, lo = hashing.mix64([jnp.asarray(vals)])
hi_np, lo_np = np.asarray(hi), np.asarray(lo)
seen, oracle = set(), []
for h, l in zip(hi_np.tolist(), lo_np.tolist()):
    oracle.append((h, l) not in seen); seen.add((h, l))
table = distributed.make_sharded_ptt(mesh, 16384)
got = []
for i in range(2):
    sl = slice(i * 8192, (i + 1) * 8192)
    table, is_new, ovf = distributed.distributed_insert(
        mesh, table,
        jax.device_put(hi_np[sl], sh), jax.device_put(lo_np[sl], sh),
        jax.device_put(np.ones(8192, bool), sh))
    assert not bool(ovf)
    got.extend(np.asarray(is_new).tolist())
out["dedup_exact"] = got == oracle
out["distinct"] = (int(np.sum(got)), len(seen))

# --- distributed PJTT + OJM probe vs python join
pk = rng.integers(0, 500, size=8192).astype(np.int32)
ps = rng.integers(0, 100000, size=8192).astype(np.int32)
ck = rng.integers(0, 700, size=8192).astype(np.int32)
idx, ovf = distributed.build_distributed_pjtt(
    mesh, jax.device_put(pk, sh), jax.device_put(ps, sh))
assert not bool(ovf)
subs, valid, ovf2 = distributed.distributed_ojm_probe(
    mesh, idx, jax.device_put(ck, sh), 128)
assert not bool(ovf2)
subs, valid = np.asarray(subs), np.asarray(valid)
d = defaultdict(set)
for k, s in zip(pk.tolist(), ps.tolist()):
    d[k].add(s)
out["join_exact"] = all(
    set(subs[i][valid[i]].tolist()) == d.get(k, set())
    for i, k in enumerate(ck.tolist()))
print(json.dumps(out))
"""


@pytest.mark.slow
def test_distributed_operators_8dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["dedup_exact"] is True
    assert out["distinct"][0] == out["distinct"][1]
    assert out["join_exact"] is True


def test_main_process_sees_one_device():
    """Guard: the test/bench environment must NOT be polluted with the
    512-device dry-run flag (assignment requirement)."""
    import jax

    assert len(jax.devices()) == 1
