"""Einsum engines — the XLA-contraction RTAC backends (no Pallas, no padding).

``einsum`` is the incremental fixpoint of Prop. 2 (the default engine);
``full`` is the paper-faithful bare recurrence of Eq. 1, recomputing the
support test for every (x, a) each step — kept as the fidelity baseline.
"""

from __future__ import annotations

import functools
from typing import List

import jax.numpy as jnp

from repro.core import rtac
from repro.core.csp import CSP
from repro.core.engine import (
    Engine,
    PreparedMany,
    PreparedNetwork,
    StackedSlotPool,
    as_changed,
    pad_pairs,
    resolve_instance_idx,
    slot_install_program,
)
from repro.core.rtac import EnforceResult, SupportFn, einsum_support
from . import register


@functools.lru_cache(maxsize=None)
def _einsum_frontier_fix(revise_fn):
    """Stable-identity fused frontier core (keys the frontier step's jit
    cache): batched assign + seed + the gather/vmap incremental fixpoint."""

    def fix(networks, doms, var, val, net_idx):
        return rtac.assign_enforce_many(networks, doms, var, val, net_idx,
                                        revise_fn=revise_fn)

    return fix


@functools.lru_cache(maxsize=None)
def _full_frontier_fix(support_fn):
    def fix(networks, doms, var, val, net_idx):
        cons, mask = networks
        return rtac.assign_enforce_full_many(cons, mask, doms, var, val, net_idx,
                                             support_fn=support_fn)

    return fix


def _stack_networks(csps: List[CSP]):
    """(B, n, n, d, d) cons + (B, n, n) mask — the stacked workload form."""
    return (
        jnp.stack([c.cons for c in csps]),
        jnp.stack([c.mask for c in csps]),
    )


def _open_einsum_pool(engine, n_vars, dom_size, capacity, round_dispatch):
    """Shared einsum/full slot pool: bool (C, n, n, d, d) / (C, n, n) tables at
    the bucket shape; the round dispatch is the same jitted gather+vmap
    fixpoint as `enforce_many`."""
    n, d = n_vars, dom_size
    tables = (
        jnp.zeros((capacity, n, n, d, d), jnp.bool_),
        jnp.zeros((capacity, n, n), jnp.bool_),
    )

    def dispatch(tables, doms, changed0, idx):
        return round_dispatch(
            tables, jnp.asarray(doms), as_changed(changed0), jnp.asarray(idx)
        )

    return StackedSlotPool(
        engine, n_vars, dom_size, capacity,
        tables, install=slot_install_program(pad_pairs, n, d), dispatch=dispatch,
    )


def _revise_for(support_fn: SupportFn):
    """Module-level-stable revise closure (keys `enforce_generic`'s jit cache)."""
    if support_fn is einsum_support:
        return rtac._EINSUM_REVISE
    return rtac._REVISE_CACHE.setdefault(support_fn, rtac.make_einsum_revise(support_fn))


@register
class EinsumEngine(Engine):
    """Incremental RTAC (Prop. 2) with the einsum support contraction."""

    name = "einsum"
    stacked_many = True
    slot_table = True
    device_frontier = True
    # stacked frontier rounds amortize extra rows — speculation is cheap here
    speculative_rows_hint = 64

    def __init__(self, support_fn: SupportFn = einsum_support):
        self.support_fn = support_fn
        self._revise_fn = _revise_for(support_fn)

    def _prepare_payload(self, csp: CSP):
        return (csp.cons, csp.mask)

    def enforce(self, prepared: PreparedNetwork, dom, changed0=None) -> EnforceResult:
        return rtac.enforce_generic(
            prepared.payload, jnp.asarray(dom), as_changed(changed0),
            revise_fn=self._revise_fn,
        )

    def enforce_batch(self, prepared: PreparedNetwork, doms, changed0=None) -> EnforceResult:
        return rtac.enforce_batch_generic(
            prepared.payload, jnp.asarray(doms), as_changed(changed0),
            revise_fn=self._revise_fn,
        )

    def _prepare_many_payload(self, csps: List[CSP]):
        return _stack_networks(csps)

    def enforce_many(self, prepared: PreparedMany, doms, changed0=None, instance_idx=None) -> EnforceResult:
        doms = jnp.asarray(doms)
        idx = resolve_instance_idx(instance_idx, prepared.n_instances, doms.shape[0])
        return rtac.enforce_many_generic(
            prepared.payload, doms, as_changed(changed0), jnp.asarray(idx),
            revise_fn=self._revise_fn,
        )

    def _open_stacked_slot_pool(self, n_vars, dom_size, capacity) -> StackedSlotPool:
        def dispatch(networks, doms, changed0, idx):
            return rtac.enforce_many_generic(
                networks, doms, changed0, idx, revise_fn=self._revise_fn
            )

        return _open_einsum_pool(self, n_vars, dom_size, capacity, dispatch)

    def frontier_fix(self):
        return _einsum_frontier_fix(self._revise_fn)

    def frontier_networks(self, prepared: PreparedMany):
        return prepared.payload


@register
class FullEngine(Engine):
    """Paper-faithful dense recurrence (Eq. 1). Ignores ``changed0`` — every
    step re-tests all (x, a) pairs, exactly as published."""

    name = "full"
    stacked_many = True
    slot_table = True
    device_frontier = True
    speculative_rows_hint = 64

    def __init__(self, support_fn: SupportFn = einsum_support):
        self.support_fn = support_fn

    def _prepare_payload(self, csp: CSP):
        return (csp.cons, csp.mask)

    def enforce(self, prepared: PreparedNetwork, dom, changed0=None) -> EnforceResult:
        cons, mask = prepared.payload
        return rtac.enforce_full(cons, mask, jnp.asarray(dom), support_fn=self.support_fn)

    def enforce_batch(self, prepared: PreparedNetwork, doms, changed0=None) -> EnforceResult:
        cons, mask = prepared.payload
        return rtac.enforce_full_batch(cons, mask, jnp.asarray(doms), support_fn=self.support_fn)

    def _prepare_many_payload(self, csps: List[CSP]):
        return _stack_networks(csps)

    def enforce_many(self, prepared: PreparedMany, doms, changed0=None, instance_idx=None) -> EnforceResult:
        doms = jnp.asarray(doms)
        idx = resolve_instance_idx(instance_idx, prepared.n_instances, doms.shape[0])
        cons, mask = prepared.payload
        return rtac.enforce_full_many(
            cons, mask, doms, jnp.asarray(idx), support_fn=self.support_fn
        )

    def _open_stacked_slot_pool(self, n_vars, dom_size, capacity) -> StackedSlotPool:
        def dispatch(networks, doms, changed0, idx):
            cons, mask = networks
            del changed0  # the paper-faithful recurrence re-tests everything
            return rtac.enforce_full_many(cons, mask, doms, idx, support_fn=self.support_fn)

        return _open_einsum_pool(self, n_vars, dom_size, capacity, dispatch)

    def frontier_fix(self):
        return _full_frontier_fix(self.support_fn)

    def frontier_networks(self, prepared: PreparedMany):
        return prepared.payload
