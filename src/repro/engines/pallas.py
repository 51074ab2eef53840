"""Pallas kernel engines — dense int8 and bitpacked int32 encodings (DESIGN.md §4).

``prepare`` pays the O(n²d²) padding / transpose / bitpack of the constraint
tensor exactly once per CSP; the hot path pads only the O(n·d) domain (and
changed seed) into kernel coordinates and un-pads the result, so callers never
see padded shapes. The closures come from the ``lru_cache``-d factories in
`repro.kernels.ops`, keyed on (encoding, interpret), so their identity is
stable and each jitted program compiles once per shape — including under
``vmap`` for ``enforce_batch``.

Whether the kernels run interpreted is decided when an engine is constructed,
from the backend (`ops.interpret_mode`): the CPU interprets, a TPU compiles.

Workload/service paths are fully device-resident (no host routing):

- ``prepare_many`` stacks the per-instance prepared networks into tables
  (``(B, N, N)`` int8 for `pallas_dense`, ``(B, W, n_p, N)`` int32 words for
  `pallas_packed`, N = n_p·d_p) and ``enforce_many`` runs ONE stacked
  fixpoint — the fused kernel, or the stepped while_loop around the stacked
  revise kernel.
- ``open_slot_pool`` backs the service with a `StackedSlotPool` over the same
  tables: installs are donated ``.at[slot].set`` row writes, and every round is
  one jitted gather + kernel dispatch. Results are bit-identical to the einsum
  slot path by construction (same coroutine, same per-row fixpoint semantics).

``network_nbytes`` reports the engine's TRUE resident footprint — padded int8
bytes for `pallas_dense`, packed words (8× less) for `pallas_packed` — so the
service cache budget admits proportionally more packed networks.
"""

from __future__ import annotations

import os

import jax.numpy as jnp

from repro.core import rtac
from repro.core.csp import CSP
from repro.core.engine import (
    Engine,
    PreparedMany,
    PreparedNetwork,
    StackedSlotPool,
    as_changed,
    pad_changed,
    pad_dom,
    resolve_instance_idx,
    slot_install_program,
)
from repro.core.rtac import EnforceResult, enforce_batch_generic, enforce_generic
from repro.kernels import autotune, ops
from . import register


class _PallasEngine(Engine):
    """Shared prepare/enforce plumbing; subclasses pick the encoding
    (``kind``: "dense" int8 or "packed" int32 words).

    ``dims`` is the kernel-coordinate tuple — (n_p, d_p) for dense,
    (n_p, d_p, w) for packed (`ops.kernel_dims`).
    """

    kind: str
    stacked_many = True
    slot_table = True
    device_frontier = True
    # stacked kernel rows are near-free up to the tile width
    speculative_rows_hint = 64

    def __init__(self, fixpoint: str | None = None):
        # decided once, from the backend: interpreted on CPU, compiled on TPU
        self.interpret = ops.interpret_mode()
        # Recurrence placement: "fused" runs the whole fixpoint inside ONE
        # kernel launch (domains resident in VMEM); "stepped" is the XLA
        # while_loop around per-iteration revise launches — kept as the
        # fallback and the parity oracle. Bit-identical by construction
        # (tests/test_fused.py sweeps both).
        if fixpoint is None:
            fixpoint = os.environ.get("REPRO_PALLAS_FIXPOINT", "fused")
        if fixpoint not in ("fused", "stepped"):
            raise ValueError(
                f"fixpoint must be 'fused' or 'stepped', got {fixpoint!r}"
            )
        self.fixpoint = fixpoint
        self.fused_fixpoint = fixpoint == "fused"

    def stepped(self) -> "_PallasEngine":
        """This engine with the stepped fixpoint — same encoding and
        interpret mode (the service ladder's middle rung)."""
        sibling = type(self)(fixpoint="stepped")
        sibling.interpret = self.interpret
        return sibling

    def _dims(self, n: int, d: int):
        return ops.kernel_dims(self.kind, n, d)

    def _prepare_net(self, csp: CSP):
        network, _, dims = ops.prepare_network(self.kind, csp)
        return network, dims

    # --- single-network path (one search, many domains) ---------------------

    def _prepare_payload(self, csp: CSP):
        network, dims = self._prepare_net(csp)
        return network, dims, ops.revise_fn(self.kind, self.interpret)

    def enforce(self, prepared: PreparedNetwork, dom, changed0=None) -> EnforceResult:
        network, dims, revise_fn = prepared.payload
        n_p, d_p = dims[0], dims[1]
        n, d = prepared.n_vars, prepared.dom_size
        dom_p = pad_dom(jnp.asarray(dom), n_p, d_p)
        ch_p = pad_changed(changed0, n, n_p)
        res = enforce_generic(network, dom_p, ch_p, revise_fn=revise_fn)
        return EnforceResult(res.dom[:n, :d], res.consistent, res.n_recurrences)

    def enforce_batch(self, prepared: PreparedNetwork, doms, changed0=None) -> EnforceResult:
        network, dims, revise_fn = prepared.payload
        n_p, d_p = dims[0], dims[1]
        n, d = prepared.n_vars, prepared.dom_size
        doms = jnp.asarray(doms)
        dom_p = pad_dom(doms, n_p, d_p)
        ch_p = pad_changed(changed0, n, n_p, batch=doms.shape[:-2])
        res = enforce_batch_generic(network, dom_p, ch_p, revise_fn=revise_fn)
        return EnforceResult(res.dom[:, :n, :d], res.consistent, res.n_recurrences)

    # --- stacked workload path (R rows, each against its OWN network) -------

    def _prepare_many_payload(self, csps):
        nets = [self._prepare_net(c) for c in csps]
        tables = (
            jnp.stack([net[0][0] for net in nets]),
            jnp.stack([net[0][1] for net in nets]),
        )
        return tables, nets[0][1]

    def _rows_dispatch(self, tables, dims, n, d, doms, changed0, idx):
        """Pad R caller-coordinate rows into kernel coordinates, run the ONE
        stacked gather+kernel fixpoint, un-pad. Shared by `enforce_many` and
        the slot pool."""
        n_p, d_p = dims[0], dims[1]
        doms = jnp.asarray(doms)
        dom_p = pad_dom(doms, n_p, d_p)
        ch_p = pad_changed(as_changed(changed0), n, n_p, batch=doms.shape[:-2])
        if self.fused_fixpoint:
            autotune.maybe_tune(self.kind, n_p, d_p, dom_p.shape[0])
            res = ops.enforce_rows_fused(
                tables, dom_p, ch_p, jnp.asarray(idx),
                fixpoint_rows_fn=ops.fixpoint_rows_fn(self.kind, self.interpret),
            )
        else:
            res = rtac.enforce_rows_generic(
                tables, dom_p, ch_p, jnp.asarray(idx),
                revise_rows_fn=ops.rows_fn(self.kind, self.interpret),
            )
        return EnforceResult(res.dom[:, :n, :d], res.consistent, res.n_recurrences)

    def enforce_many(
        self, prepared: PreparedMany, doms, changed0=None, instance_idx=None
    ) -> EnforceResult:
        tables, dims = prepared.payload
        idx = resolve_instance_idx(
            instance_idx, prepared.n_instances, len(doms)
        )
        return self._rows_dispatch(
            tables, dims, prepared.n_vars, prepared.dom_size, doms, changed0, idx
        )

    def _open_stacked_slot_pool(self, n_vars, dom_size, capacity) -> StackedSlotPool:
        dims = self._dims(n_vars, dom_size)

        def dispatch(tables, doms, changed0, idx):
            return self._rows_dispatch(tables, dims, n_vars, dom_size, doms, changed0, idx)

        return StackedSlotPool(
            self, n_vars, dom_size, capacity,
            self._empty_tables(dims, capacity),
            install=slot_install_program(ops.encode_network, self.kind, dims[0], dims[1]),
            dispatch=dispatch,
        )

    # --- device-resident frontiers (DESIGN.md §8) ---------------------------

    def frontier_fix(self):
        """The `lru_cache`-d fused assign+enforce entry from `kernels.ops`
        (stable identity per (encoding, fixpoint, interpret) — keys
        the frontier step's jit cache); kernel dims derive from the row shapes
        at trace time, so one fix object serves every bucket. In fused mode
        the whole round's recurrence is one kernel launch."""
        return ops.frontier_fn(self.kind, self.fused_fixpoint, self.interpret)

    def frontier_networks(self, prepared: PreparedMany):
        return prepared.payload[0]


@register
class PallasDenseEngine(_PallasEngine):
    """Incremental RTAC with the dense int8 Pallas kernels (MXU support count)."""

    name = "pallas_dense"
    kind = "dense"

    def _empty_tables(self, dims, capacity: int):
        n_p, d_p = dims
        return (
            jnp.zeros((capacity, n_p * d_p, n_p * d_p), jnp.int8),
            jnp.zeros((capacity, n_p, n_p), jnp.uint8),
        )

    def network_nbytes(self, n_vars: int, dom_size: int) -> int:
        n_p, d_p = self._dims(n_vars, dom_size)
        return n_p * d_p * n_p * d_p + n_p * n_p  # int8 cons + u8 mask


@register
class PallasPackedEngine(_PallasEngine):
    """Incremental RTAC with the bitpacked Pallas kernels (32 values per
    word: 8× less constraint traffic than int8, 16× than bf16)."""

    name = "pallas_packed"
    kind = "packed"

    def _empty_tables(self, dims, capacity: int):
        n_p, d_p, w = dims
        return (
            jnp.zeros((capacity, w, n_p, n_p * d_p), jnp.int32),
            jnp.zeros((capacity, n_p, n_p), jnp.uint8),
        )

    def network_nbytes(self, n_vars: int, dom_size: int) -> int:
        n_p, d_p, w = self._dims(n_vars, dom_size)
        return n_p * d_p * n_p * w * 4 + n_p * n_p  # int32 packed words + u8 mask
