"""Ahead-of-time compile of the sharded RTAC enforcer for a four-chip v5e host.

Compiles `core/sharded.py`'s shard_map fixpoint for a *described* ``v5e:2x2``
topology (no chip needed: the TPU compiler runs here and refuses what the
chip would refuse), with the constraint x-rows sharded over the 4 chips'
'model' axis and a batch of search-node domains replicated over 'data'.
Prints per-device memory, cost per recurrence, and the collectives the
compiler put in, and writes one JSON record per variant under
``artifacts/dryrun/``.

Variants (the encoding of the support test):
  einsum-bf16   paper-faithful tensorized contraction (matmul on the MXU)
  einsum-u8     dense uint8 support test (2× less traffic)
  bitpacked     uint32 AND/any words (16× less constraint traffic than bf16)

Note on counting: the fixpoint is a `while` loop whose body XLA counts once —
all numbers below are therefore PER RECURRENCE.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.dryrun_rtac [--n 4096 --d 32]
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.engines import ShardedEngine
from repro.launch.mesh import make_mesh
from repro.parallel.hlo_stats import collective_stats, total_wire_bytes

ART_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"
TOPOLOGY = "v5e:2x2"


def _mem_dict(compiled) -> dict:
    """Numeric fields of ``compiled.memory_analysis()`` (backend-dependent
    attribute set, so reflect rather than enumerate)."""
    out = {}
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # backend without memory analysis
        return {"error": str(e)}
    if ma is None:
        return {"error": "memory_analysis() returned None"}
    for k in dir(ma):
        if k.startswith("_"):
            continue
        try:
            v = getattr(ma, k)
        except Exception:
            continue
        if isinstance(v, (int, float)):
            out[k] = v
    return out


def _cost_dict(compiled) -> dict:
    """Numeric fields of ``compiled.cost_analysis()`` (list-wrapped on some
    backends)."""
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        return {"error": str(e)}
    if ca is None:
        return {"error": "cost_analysis() returned None"}
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {k: float(v) for k, v in ca.items() if isinstance(v, (int, float))}


def compile_variant(variant: str, devices, n_vars: int, dom: int, batch: int):
    """Lower + compile the sharded enforcer for ``devices`` on shapes only
    (no constraint tensor is allocated). Returns the compiled executable."""
    mesh = make_mesh((1, len(devices)), ("data", "model"), devices=devices)
    impl = "bitpacked" if variant == "bitpacked" else "einsum"
    dtype = {"einsum-bf16": jnp.bfloat16, "einsum-u8": jnp.uint8}.get(variant, jnp.bfloat16)
    eng = ShardedEngine(mesh=mesh, dtype=dtype, impl=impl)
    model = NamedSharding(mesh, P("model"))
    data = NamedSharding(mesh, P("data"))
    if impl == "bitpacked":
        cons = jax.ShapeDtypeStruct((n_vars, n_vars, dom, -(-dom // 32)), jnp.uint32,
                                    sharding=model)
    else:
        cons = jax.ShapeDtypeStruct((n_vars, n_vars, dom, dom), jnp.bool_, sharding=model)
    mask = jax.ShapeDtypeStruct((n_vars, n_vars), jnp.bool_, sharding=model)
    doms = jax.ShapeDtypeStruct((batch, n_vars, dom), jnp.bool_, sharding=data)
    ch = jax.ShapeDtypeStruct((batch, n_vars), jnp.bool_, sharding=data)
    return eng.build_enforcer().lower(cons, mask, doms, ch).compile()


def run_variant(variant: str, devices, n_vars: int, dom: int, batch: int) -> dict:
    t0 = time.time()
    compiled = compile_variant(variant, devices, n_vars, dom, batch)
    t_compile = time.time() - t0
    coll = collective_stats(compiled.as_text())
    rec = {
        "workload": "rtac",
        "variant": variant,
        "topology": TOPOLOGY,
        "n_vars": n_vars,
        "dom": dom,
        "batch": batch,
        "n_devices": len(devices),
        "compile_s": round(t_compile, 2),
        "memory_analysis": _mem_dict(compiled),
        "cost_analysis": _cost_dict(compiled),  # per recurrence (while body)
        "collectives": coll,
        "collective_wire_bytes": total_wire_bytes(coll),
    }
    ca = rec["cost_analysis"]
    mem = rec["memory_analysis"]
    print(
        f"[dryrun-rtac] {variant:12s} on {TOPOLOGY}: compile {t_compile:.1f}s "
        f"flops/dev={ca.get('flops', 0):.3e} bytes/dev={ca.get('bytes accessed', 0):.3e} "
        f"wire/dev={rec['collective_wire_bytes']:.3e}B "
        f"temp={mem.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
        f"args={mem.get('argument_size_in_bytes', 0)/2**30:.2f}GiB",
        flush=True,
    )
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=4096, help="variables")
    ap.add_argument("--d", type=int, default=32, help="domain size")
    ap.add_argument("--batch", type=int, default=64, help="domains per enforcement")
    ap.add_argument("--variants", default="einsum-bf16,einsum-u8,bitpacked")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    devices = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY).devices
    ART_DIR.mkdir(parents=True, exist_ok=True)
    for variant in args.variants.split(","):
        rec = run_variant(variant, devices, args.n, args.d, args.batch)
        path = ART_DIR / f"rtac__{variant}__n{args.n}_d{args.d}.json"
        path.write_text(json.dumps(rec, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
