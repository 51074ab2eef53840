"""Distributed RTAC: shard the constraint tensor over a (data, model) mesh.

The mesh spans every device the process has: 8 emulated host devices on a
CPU (set below), or the chips of a TPU host (the same shard_map program runs
unchanged). Constraint-tensor x-rows are sharded over 'model', a batch of
candidate domains (search nodes) over 'data'.

    PYTHONPATH=src python examples/distributed_ac.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import numpy as np

from repro.core import random_csp
from repro.engines import get_engine
from repro.launch.mesh import make_mesh


def main():
    n_dev = jax.device_count()
    n_data = 2 if n_dev % 2 == 0 and n_dev > 2 else 1
    mesh = make_mesh((n_data, n_dev // n_data), ("data", "model"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"on {jax.device_count()} devices")

    csp = random_csp(n_vars=64, dom_size=16, density=0.5, tightness=0.35, seed=0)
    B = 8
    rng = np.random.default_rng(0)
    doms = np.tile(np.asarray(csp.dom)[None], (B, 1, 1))
    for i in range(B):  # perturb: simulate B search nodes
        var = rng.integers(64)
        keep = rng.integers(16)
        doms[i, var, :] = False
        doms[i, var, keep] = True

    # prepare once: shards the constraint x-rows over 'model' and builds the
    # jitted shard_map fixpoint; the hot path ships only the domain batch
    prepared = get_engine("sharded", mesh=mesh).prepare(csp)
    res = prepared.enforce_batch(doms)  # compile+run
    res.dom.block_until_ready()
    t0 = time.perf_counter()
    res = prepared.enforce_batch(doms)
    res.dom.block_until_ready()
    dt = time.perf_counter() - t0
    print(f"batch of {B} enforcements: {1e3*dt:.1f} ms "
          f"(consistent: {np.asarray(res.consistent).tolist()})")

    # verify against the single-device path
    ref_prepared = get_engine("einsum").prepare(csp)
    for i in range(B):
        ref = ref_prepared.enforce(doms[i])
        assert bool(ref.consistent) == bool(res.consistent[i])
        if bool(ref.consistent):
            assert (np.asarray(ref.dom) == np.asarray(res.dom[i])).all()
    print("sharded results == single-device results ✓")


if __name__ == "__main__":
    main()
