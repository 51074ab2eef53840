"""End-to-end behaviour tests for the paper's system (RTAC pipeline)."""

from pathlib import Path

import pytest

import numpy as np
import jax.numpy as jnp

from repro.core import (
    check_solution,
    enforce,
    enforce_ac3,
    mac_solve,
    random_csp,
)


def test_paper_pipeline_end_to_end():
    """Generate (paper §5.2) -> enforce (Alg. 1) -> search (Alg. 2) -> verify."""
    csp = random_csp(n_vars=30, dom_size=8, density=0.4, tightness=0.25, seed=0)
    res = enforce(csp.cons, csp.mask, csp.dom)
    assert bool(res.consistent)
    sol, stats = mac_solve(csp, engine="einsum")
    assert sol is not None and check_solution(csp, sol)
    assert stats.mean_recurrences < 8


def test_recurrences_much_smaller_than_revisions():
    """The paper's headline claim (Table 1): #Recurrence << #Revision, and
    #Recurrence stays ~flat as density grows. Runs through the sweep
    harness's assignments mode — the committed ``recurrence_density`` study
    uses this exact cell executor."""
    from repro.sweeps import SweepSpec
    from repro.sweeps.runner import _run_assignments_cell

    spec = SweepSpec(
        name="t_table1", mode="assignments", replicates=1,
        problem={
            "family": "random_binary",
            "knobs": {"n": 100, "d": 20, "tightness": 0.3,
                      "density": [0.25, 0.75]},
        },
        solver={"engine": ["einsum", "ac3"], "n_assignments": 5,
                "batch_timing": False},
    )
    counts = {}  # (engine, density) -> mean count
    for cell in spec.cells():
        # engine is excluded from the workload seed, so both engines
        # enforce the same sampled sites of the same instance
        m = _run_assignments_cell(spec, cell, spec.workload_seed(cell))
        assert m["roots_consistent"] == m["n_instances"], m
        flat = cell.flat()
        counts[(flat["engine"], flat["density"])] = m["mean_count"]
    recs = [counts[("einsum", d)] for d in (0.25, 0.75)]
    revs = [counts[("ac3", d)] for d in (0.25, 0.75)]
    assert all(k <= 6 for k in recs), recs
    assert all(r > 10 * k for r, k in zip(revs, recs)), (revs, recs)
    # revisions grow with density; recurrences roughly flat (paper Table 1)
    assert revs[1] > revs[0]
    assert abs(recs[1] - recs[0]) < 3.0


def test_sharded_enforcer_multidevice_subprocess():
    """Spawn a subprocess with 8 host devices: shard_map RTAC == reference."""
    import subprocess, sys, textwrap

    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import random_csp, enforce
        from repro.core.sharded import make_sharded_enforcer, shard_csp_arrays
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        csp = random_csp(16, 8, 0.7, 0.4, seed=3)
        B = 4
        dom_b = jnp.tile(csp.dom[None], (B, 1, 1))
        dom_b = dom_b.at[1, 0, :4].set(False)
        dom_b = dom_b.at[2, 5, 1:].set(False)
        changed_b = jnp.ones((B, 16), jnp.bool_)
        enf = make_sharded_enforcer(mesh)
        cons_s, mask_s, dom_s = shard_csp_arrays(mesh, csp.cons, csp.mask, dom_b)
        res = enf(cons_s, mask_s, dom_s, changed_b)
        for i in range(B):
            ref = enforce(csp.cons, csp.mask, dom_b[i])
            assert bool(ref.consistent) == bool(res.consistent[i])
            if bool(ref.consistent):
                assert (np.asarray(ref.dom) == np.asarray(res.dom[i])).all()
        print("SHARDED_OK")
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=str(Path(__file__).resolve().parents[1]),
        timeout=600,
    )
    assert "SHARDED_OK" in out.stdout, out.stderr[-2000:]


@pytest.mark.parametrize("placed", [False, True])
def test_compile_cache_placement_subprocess(tmp_path, placed):
    """The entry points' compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set (nothing set in code), else the fixed ``<checkout>/.jax_cache``."""
    import os
    import subprocess
    import sys

    root = Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(root / "src"), JAX_PLATFORMS="cpu")
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    code = (
        "import jax; from repro.launch import compile_cache; "
        "print(compile_cache.enable()); print(jax.config.jax_compilation_cache_dir)"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=str(tmp_path), timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    returned, configured = out.stdout.split()
    want = str(tmp_path / "cc") if placed else str(root / ".jax_cache")
    assert returned == configured == want
