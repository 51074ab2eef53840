"""RTAC — Recurrent Tensor Arc Consistency enforcement (paper Eq. 1 / Alg. 1).

The whole fixpoint runs as ONE XLA program (``lax.while_loop``), in contrast to
the paper's PyTorch loop which syncs with the host every recurrence. Two variants:

- :func:`enforce_full` — the bare recurrence of Eq. 1: every step recomputes the
  support test for all (x, a) pairs. This is the *paper-faithful dense baseline*.
- :func:`enforce` — the incremental variant licensed by Proposition 2: a value can
  only die because a *last-step-deleted* support vanished, so the revision test is
  masked to neighbours whose domain changed. On TPU (static shapes) the paper's
  ``changed_idx`` gather becomes a boolean mask; see DESIGN.md §2.

Both are jittable, ``vmap``-able over a batch of domains (shared network), and
take a pluggable ``support_fn`` so the Pallas kernels (`repro.kernels`) can
replace the einsum contraction.

Support-test convention (DESIGN.md §2): ``cons`` holds zero blocks for
unconstrained pairs and ``mask`` marks real constraints, so

    has_support[x, y, a] = (Σ_b cons[x,y,a,b]·dom[y,b] > 0) | ~mask[x, y]

which is identical to the paper's all-ones-block encoding.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .csp import CSP

Array = jax.Array

# support_fn(cons, mask, dom) -> has_support bool (n, n, d):
#   has_support[x, y, a] == (x,a) has a support in dom(y) under c_xy, or x,y unconstrained
SupportFn = Callable[[Array, Array, Array], Array]


def einsum_support(cons: Array, mask: Array, dom: Array, dtype=jnp.bfloat16) -> Array:
    """Reference contraction — the paper's ``matmul`` (Alg. 1 line 14) in einsum form.

    bf16 is exact here: we only test count > 0, and partial sums ≤ d fit the
    MXU accumulator (f32 accumulation in XLA dots).
    """
    cnt = jnp.einsum(
        "xyab,yb->xya",
        cons.astype(dtype),
        dom.astype(dtype),
        preferred_element_type=jnp.float32,
    )
    return (cnt > 0) | ~mask[:, :, None]


class EnforceResult(NamedTuple):
    dom: Array  # (n, d) bool — the AC closure D_ac (valid only if consistent)
    consistent: Array  # () bool — False iff some domain wiped out
    n_recurrences: Array  # () int32 — K of Eq. 1 (Table 1 "#Recurrence")
    #: from a fixpoint that streams its network from HBM only (else None):
    #: variables seeded for revision, summed over the recurrences (int32),
    #: and the network and mask bytes it read (float32)
    revised: Optional[Array] = None
    streamed: Optional[Array] = None


class _State(NamedTuple):
    dom: Array
    changed: Array  # (n,) bool — variables whose domain shrank last step
    consistent: Array
    k: Array


def _cond(state: _State) -> Array:
    return jnp.logical_and(state.consistent, jnp.any(state.changed))


# revise_fn(network, dom, changed) -> violated (n, d) bool:
#   violated[x,a] == some *changed* neighbour y offers no support for (x,a).
# ``network`` is an opaque pytree owned by the revise implementation — (cons, mask)
# for the einsum/dense paths, bitpacked words for the packed kernel.
ReviseFn = Callable


def make_einsum_revise(support_fn: SupportFn = einsum_support) -> ReviseFn:
    def revise(network, dom, changed):
        cons, mask = network
        has = support_fn(cons, mask, dom)  # (n, n, d)
        # (x,a) dies iff some *changed* neighbour y offers no support (Alg.1 l.16).
        return jnp.any(changed[None, :, None] & ~has, axis=1)  # (n, d)

    return revise


def _step(network, revise_fn, state: _State) -> _State:
    violated = revise_fn(network, state.dom, state.changed)
    new_dom = state.dom & ~violated
    changed = jnp.any(new_dom != state.dom, axis=-1)  # (n,)
    consistent = ~jnp.any(jnp.sum(new_dom, axis=-1) == 0)  # Alg.1 line 6
    return _State(new_dom, changed, consistent, state.k + 1)


@functools.partial(jax.jit, static_argnames=("revise_fn",))
def enforce_generic(
    network,
    dom: Array,
    changed0: Optional[Array] = None,
    revise_fn: ReviseFn = make_einsum_revise(),
) -> EnforceResult:
    """Incremental RTAC (Prop. 2) over an opaque network representation."""
    n = dom.shape[0]
    if changed0 is None:
        changed0 = jnp.ones((n,), dtype=jnp.bool_)
    # Initial wipeout check (a variable may start with an empty domain).
    consistent0 = ~jnp.any(jnp.sum(dom, axis=-1) == 0)
    state = _State(
        dom=dom,
        changed=changed0 & consistent0,
        consistent=consistent0,
        k=jnp.zeros((), jnp.int32),
    )
    body = functools.partial(_step, network, revise_fn)
    final = lax.while_loop(_cond, body, state)
    return EnforceResult(final.dom, final.consistent, final.k)


_EINSUM_REVISE = make_einsum_revise()
_REVISE_CACHE: dict = {}


def enforce(
    cons: Array,
    mask: Array,
    dom: Array,
    changed0: Optional[Array] = None,
    support_fn: SupportFn = einsum_support,
) -> EnforceResult:
    """Incremental RTAC (Prop. 2). ``changed0`` seeds the revision set — all
    variables for a fresh network, ``one_hot(idx)`` after an assignment (Alg. 2).

    ``support_fn`` must be a module-level function (it keys the jit cache)."""
    if support_fn is einsum_support:
        revise_fn = _EINSUM_REVISE
    else:
        revise_fn = _REVISE_CACHE.setdefault(support_fn, make_einsum_revise(support_fn))
    return enforce_generic((cons, mask), dom, changed0, revise_fn=revise_fn)


def _step_full(cons, mask, support_fn, state: _State) -> _State:
    has = support_fn(cons, mask, state.dom)
    alive = jnp.all(has, axis=1)  # (n, d): supported on EVERY neighbour
    new_dom = state.dom & alive
    changed = jnp.any(new_dom != state.dom, axis=-1)
    consistent = ~jnp.any(jnp.sum(new_dom, axis=-1) == 0)
    return _State(new_dom, changed, consistent, state.k + 1)


@functools.partial(jax.jit, static_argnames=("support_fn",))
def enforce_full(
    cons: Array,
    mask: Array,
    dom: Array,
    support_fn: SupportFn = einsum_support,
) -> EnforceResult:
    """Paper-faithful dense recurrence (Eq. 1, no incrementality)."""
    n = dom.shape[0]
    consistent0 = ~jnp.any(jnp.sum(dom, axis=-1) == 0)
    state = _State(
        dom=dom,
        changed=jnp.ones((n,), jnp.bool_) & consistent0,
        consistent=consistent0,
        k=jnp.zeros((), jnp.int32),
    )
    body = functools.partial(_step_full, cons, mask, support_fn)
    final = lax.while_loop(_cond, body, state)
    return EnforceResult(final.dom, final.consistent, final.k)


@functools.partial(jax.jit, static_argnames=("support_fn",))
def enforce_full_batch(
    cons: Array,
    mask: Array,
    dom: Array,  # (B, n, d)
    support_fn: SupportFn = einsum_support,
) -> EnforceResult:
    """Batched paper-faithful recurrence: B domains, one shared network."""
    fn = functools.partial(enforce_full.__wrapped__, cons, mask, support_fn=support_fn)
    return jax.vmap(fn)(dom)


# ---------------------------------------------------------------------------
# Batched enforcement — the beyond-paper throughput lever (DESIGN.md §2):
# one shared network, B candidate domains (search nodes / restarts) enforced
# simultaneously. vmap-of-while_loop runs until the *slowest* node converges;
# converged nodes no-op (the revision is idempotent), so correctness holds.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("revise_fn",))
def enforce_batch_generic(
    network,
    dom: Array,  # (B, n, d)
    changed0: Optional[Array] = None,  # (B, n) or None
    revise_fn: ReviseFn = _EINSUM_REVISE,
) -> EnforceResult:
    fn = functools.partial(enforce_generic.__wrapped__, revise_fn=revise_fn)
    if changed0 is None:
        return jax.vmap(lambda d: fn(network, d))(dom)
    return jax.vmap(lambda d, c: fn(network, d, c))(dom, changed0)


def enforce_batch(
    cons: Array,
    mask: Array,
    dom: Array,  # (B, n, d)
    changed0: Optional[Array] = None,  # (B, n) or None
    support_fn: SupportFn = einsum_support,
) -> EnforceResult:
    if support_fn is einsum_support:
        revise_fn = _EINSUM_REVISE
    else:
        revise_fn = _REVISE_CACHE.setdefault(support_fn, make_einsum_revise(support_fn))
    return enforce_batch_generic((cons, mask), dom, changed0, revise_fn=revise_fn)


# ---------------------------------------------------------------------------
# Multi-instance enforcement — R domains, each against its OWN network.
# ``networks`` is a pytree whose leaves carry a leading instance axis (B, ...)
# (B stacked constraint networks sharing (n, d)); ``instance_idx ∈ [0,B)^R``
# maps each domain row to its network. One vmapped fixpoint resolves a whole
# workload of independent CSPs in a single device dispatch (DESIGN.md §6).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("revise_fn",))
def enforce_many_generic(
    networks,
    dom: Array,  # (R, n, d)
    changed0: Optional[Array],  # (R, n) or None
    instance_idx: Array,  # (R,) int32
    revise_fn: ReviseFn = _EINSUM_REVISE,
) -> EnforceResult:
    net = jax.tree_util.tree_map(lambda a: a[instance_idx], networks)
    fn = functools.partial(enforce_generic.__wrapped__, revise_fn=revise_fn)
    if changed0 is None:
        return jax.vmap(lambda nw, d: fn(nw, d))(net, dom)
    return jax.vmap(lambda nw, d, c: fn(nw, d, c))(net, dom, changed0)


# revise_rows_fn(net_g, doms, changed) -> violated (R, n, d) bool — the stacked
# analogue of ReviseFn: ``net_g`` is a pytree whose leaves carry a leading row
# axis (row i's network, already gathered), and row i is revised against its
# own network. The Pallas stacked kernels bind here (`repro.kernels.ops`).
ReviseRowsFn = Callable


class _RowState(NamedTuple):
    dom: Array  # (R, n, d)
    changed: Array  # (R, n)
    consistent: Array  # (R,)
    k: Array  # (R,) int32


@functools.partial(jax.jit, static_argnames=("revise_rows_fn",))
def enforce_rows_generic(
    networks,
    dom: Array,  # (R, n, d)
    changed0: Optional[Array],  # (R, n) or None
    instance_idx: Array,  # (R,) int32
    revise_rows_fn: ReviseRowsFn,
) -> EnforceResult:
    """R incremental fixpoints, row i against ``networks[instance_idx[i]]``,
    as ONE while_loop over a *stacked* revise (no vmap): every step revises all
    still-active rows in a single stacked-kernel launch. Per-row results are
    bit-identical to running `enforce_generic` on each row alone — a row is
    *active* while ``consistent & any(changed)`` (exactly the solo loop
    predicate), an inactive row's revision seed is zeroed (the incremental
    revise is then a no-op, freezing its domain), and ``k`` counts only the
    steps the row was active — so per-row recurrence counts match solo runs
    even though the loop runs until the slowest row converges.
    """
    net = jax.tree_util.tree_map(lambda a: a[instance_idx], networks)
    r, n, _ = dom.shape
    if changed0 is None:
        changed0 = jnp.ones((r, n), dtype=jnp.bool_)
    consistent0 = ~jnp.any(jnp.sum(dom, axis=-1) == 0, axis=-1)  # (R,)
    state = _RowState(
        dom=dom,
        changed=changed0 & consistent0[:, None],
        consistent=consistent0,
        k=jnp.zeros((r,), jnp.int32),
    )

    def cond(s: _RowState) -> Array:
        return jnp.any(s.consistent & jnp.any(s.changed, axis=-1))

    def body(s: _RowState) -> _RowState:
        active = s.consistent & jnp.any(s.changed, axis=-1)  # (R,)
        violated = revise_rows_fn(net, s.dom, s.changed & active[:, None])
        new_dom = s.dom & ~violated
        changed = jnp.any(new_dom != s.dom, axis=-1)
        consistent = s.consistent & ~jnp.any(jnp.sum(new_dom, axis=-1) == 0, axis=-1)
        return _RowState(new_dom, changed, consistent, s.k + active.astype(jnp.int32))

    final = lax.while_loop(cond, body, state)
    return EnforceResult(final.dom, final.consistent, final.k)


@functools.partial(jax.jit, static_argnames=("support_fn",))
def enforce_full_many(
    cons: Array,  # (B, n, n, d, d)
    mask: Array,  # (B, n, n)
    dom: Array,  # (R, n, d)
    instance_idx: Array,  # (R,) int32
    support_fn: SupportFn = einsum_support,
) -> EnforceResult:
    fn = functools.partial(enforce_full.__wrapped__, support_fn=support_fn)
    return jax.vmap(lambda c, m, d: fn(c, m, d))(
        cons[instance_idx], mask[instance_idx], dom
    )


# ---------------------------------------------------------------------------
# Fused assign + revise — the frontier dispatch (DESIGN.md §8).
# A search round no longer ships domains: the device gathers each row's parent
# closure, applies the Alg. 2 assignment, seeds the Prop. 2 revision set, and
# runs the stacked fixpoint — all inside ONE traced program.
# ---------------------------------------------------------------------------


def assign_and_seed(doms: Array, var: Array, val: Array) -> Tuple[Array, Array]:
    """Batched Alg. 2 ``assign`` fused with the Prop. 2 revision seed.

    Row i collapses ``dom(var[i])`` to ``{val[i]}`` and seeds
    ``changed = one_hot(var[i])``; ``var[i] < 0`` marks a *root* row — the
    domain is left untouched and every variable is seeded (a fresh network).
    Returns (doms', changed) of shapes (R, n, d) / (R, n)."""
    r, n, _ = doms.shape
    is_root = var < 0
    safe_var = jnp.maximum(var, 0)
    assigned = jax.vmap(assign)(doms, safe_var, val)
    doms = jnp.where(is_root[:, None, None], doms, assigned)
    onehot = jnp.arange(n, dtype=var.dtype)[None, :] == safe_var[:, None]
    changed = jnp.where(is_root[:, None], jnp.ones((r, n), jnp.bool_), onehot)
    return doms, changed


def assign_enforce_many(
    networks,
    doms: Array,  # (R, n, d) parent closures
    var: Array,  # (R,) int32; < 0 = root row (no assignment, all-changed seed)
    val: Array,  # (R,) int32
    instance_idx: Array,  # (R,) int32
    revise_fn: ReviseFn = _EINSUM_REVISE,
) -> EnforceResult:
    """Fused frontier dispatch for the contraction engines: assignment + seed
    + the gather/vmap incremental fixpoint of `enforce_many_generic`, one
    traced program (called from inside the jitted frontier step)."""
    doms, changed = assign_and_seed(doms, var, val)
    return enforce_many_generic(networks, doms, changed, instance_idx, revise_fn=revise_fn)


def assign_enforce_full_many(
    cons: Array,
    mask: Array,
    doms: Array,
    var: Array,
    val: Array,
    instance_idx: Array,
    support_fn: SupportFn = einsum_support,
) -> EnforceResult:
    """Fused frontier dispatch for the paper-faithful recurrence (Eq. 1 ignores
    the revision seed — every step re-tests all pairs, exactly as published)."""
    doms, _ = assign_and_seed(doms, var, val)
    return enforce_full_many(cons, mask, doms, instance_idx, support_fn=support_fn)


# CSP-level conveniences ------------------------------------------------------


def assign(dom: Array, var_idx, val_idx) -> Array:
    """Alg. 2 ``assign``: collapse dom(var) to {val} (traced-index safe)."""
    n, d = dom.shape
    row = jnp.zeros((d,), dom.dtype).at[val_idx].set(True)
    return dom.at[var_idx].set(row)
