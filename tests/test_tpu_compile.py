"""Ahead-of-time compiles of the main path's kernels for a described v5e.

Interpret mode hides what Mosaic refuses (lane-splitting reshapes, blocks
that break the (8, 128) rule, value-level dynamic slices, VMEM over the
scoped limit). These tests hand the real TPU compiler the kernels at the
service bucket's width (64, 32) — which covers the frb50-23 Model RB width —
and at tier-1 shapes, for a ``v5e:2x2`` topology that is described, not
attached, and check a Mosaic kernel landed in the program. Nothing runs.

The topology is described inside a module fixture (only one process may
load the TPU library, and only the worker given this file does); every
compile runs with the persistent compilation cache off, since a cache entry
written for a described chip cannot be read back without one.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.engine import _frontier_step
from repro.kernels import ops, rtac_support


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the installed libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(encoding, n, d, r, sharding):
    nd = n * d
    if encoding == "dense":
        cons = _sds((r, nd, nd), jnp.int8, sharding)
    else:
        cons = _sds((r, rtac_support.words_per_domain(d), n, nd), jnp.int32, sharding)
    return (
        cons,
        _sds((r, 1, nd), jnp.int32, sharding),
        _sds((r, n, 1), jnp.int32, sharding),
        _sds((r, n, nd), jnp.int8, sharding),
    )


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize(
    "launch,encoding,n,d",
    [
        ("fixpoint", "dense", 64, 32),  # service bucket (64, 32), fused
        ("fixpoint", "packed", 64, 32),
        ("fixpoint", "packed", 128, 64),  # bucket (128, 64): packed fits VMEM
        ("revise", "dense", 64, 32),  # stepped stacked
        ("revise", "packed", 24, 40),  # stepped stacked, two words per domain
    ],
)
def test_stacked_kernels_compile_for_v5e(one_chip, launch, encoding, n, d):
    block_r = rtac_support.max_block_r(encoding, n, d)
    assert block_r >= 1
    fn = rtac_support.fixpoint_rows if launch == "fixpoint" else rtac_support.revise_rows
    kernel = functools.partial(fn, encoding=encoding, d=d, block_r=block_r, interpret=False)
    compiled = _compile(kernel, *_kernel_args(encoding, n, d, 2 * block_r, one_chip))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0


def test_dense_bucket_128_64_is_over_the_vmem_budget():
    # one dense row at (128, 64) is a 64 MiB int8 network, double-buffered:
    # it must stream from HBM, which these kernels do not do yet
    assert rtac_support.max_block_r("dense", 128, 64) == 0
    with pytest.raises(ValueError, match="VMEM"):
        rtac_support._compiler_params("dense", 128, 64, 1)


def test_single_network_revise_compiles_for_v5e(one_chip):
    n_p, d_p = 16, 8
    net = (
        _sds((1, n_p, n_p * d_p), jnp.int32, one_chip),
        _sds((n_p, n_p), jnp.uint8, one_chip),
    )
    revise = ops.revise_fn("packed", False)
    _compile(
        lambda net, dom, ch: revise((net[0], net[1]), dom, ch),
        net,
        _sds((n_p, d_p), jnp.bool_, one_chip),
        _sds((n_p,), jnp.bool_, one_chip),
    )


def test_frontier_step_with_packed_fused_fix_compiles_for_v5e(one_chip):
    # solve_many's round program at the frb50-23 width: 16 instances, 16 rows
    n, d, rows, cap = 50, 23, 16, 64
    n_p, d_p, w = ops.kernel_dims("packed", n, d)
    nets = (
        _sds((16, w, n_p, n_p * d_p), jnp.int32, one_chip),
        _sds((16, n_p, n_p), jnp.uint8, one_chip),
    )
    idx = _sds((rows,), jnp.int32, one_chip)
    step = functools.partial(
        _frontier_step.__wrapped__, fix=ops.frontier_fn("packed", True, False)
    )
    _compile(
        step,
        _sds((cap, n, d), jnp.bool_, one_chip),
        _sds((cap, n), jnp.bool_, one_chip),
        nets, idx, idx, idx, idx, idx,
    )


@pytest.mark.parametrize("impl", ["einsum", "bitpacked"])
def test_sharded_enforcer_compiles_on_four_chips(topo, impl):
    from repro.engines import ShardedEngine
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    n, d, batch = 256, 16, 8
    model, data = NamedSharding(mesh, P("model")), NamedSharding(mesh, P("data"))
    if impl == "bitpacked":
        cons = _sds((n, n, d, 1), jnp.uint32, model)
    else:
        cons = _sds((n, n, d, d), jnp.bool_, model)
    enforcer = ShardedEngine(mesh=mesh, impl=impl).build_enforcer()
    compiled = enforcer.lower(
        cons,
        _sds((n, n), jnp.bool_, model),
        _sds((batch, n, d), jnp.bool_, data),
        _sds((batch, n), jnp.bool_, data),
    ).compile()
    text = compiled.as_text()
    assert "all-gather" in text
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_frontier_step_streaming_its_networks_compiles_for_v5e(one_chip):
    # solve_many's round program at rb600-batch8's width: 8 instances of
    # (600, 32), whose 57.6 MB a row cannot sit in VMEM, so the fused
    # fixpoint streams each network from HBM
    n, d, b, rows, cap = 600, 32, 8, 128, 8192
    n_p, d_p, w = ops.kernel_dims("packed", n, d)
    assert rtac_support.max_block_r("packed", n_p, d_p) == 0
    nets = (
        _sds((b, w, n_p, n_p * d_p), jnp.int32, one_chip),
        _sds((b, n_p, n_p), jnp.uint8, one_chip),
    )
    idx = _sds((rows,), jnp.int32, one_chip)
    step = functools.partial(
        _frontier_step.__wrapped__, fix=ops.frontier_fn("packed", True, False)
    )
    compiled = _compile(
        step,
        _sds((cap, n, d), jnp.bool_, one_chip),
        _sds((cap, n), jnp.bool_, one_chip),
        nets, idx, idx, idx, idx, idx,
    )
    assert "rtac_fixpoint_packed_stream" in compiled.as_text()
