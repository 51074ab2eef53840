"""The Engine protocol — prepare-once, enforce-many arc consistency (DESIGN.md §3).

Every enforcement backend (einsum, paper-faithful full recompute, Pallas
kernels, sharded, AC3) satisfies one small contract:

    engine.prepare(csp)            -> PreparedNetwork       (expensive, once)
    prepared.enforce(dom, ch)      -> EnforceResult         (hot path)
    prepared.enforce_batch(doms, ch) -> EnforceResult       (B domains at once)
    engine.prepare_many(csps)      -> PreparedMany          (stacked workload)
    many.enforce_many(doms, ch, idx) -> EnforceResult       (R domains, each
                                                             vs its OWN network)

``prepare`` does everything that depends only on the *constraint network*:
padding the O(n²d²) constraint tensor to kernel tiles, bitpacking, reshaping,
device placement / sharding, and constructing the (jit-cache-stable) revise
closure. The per-call path touches only O(n·d) domain data. MAC search
(`core/search.py`) calls ``prepare`` exactly once per CSP and then enforces
thousands of candidate domains against the same prepared network — previously
the kernel paths re-padded and re-bitpacked the constraint tensor on every
single enforcement.

``enforce``/``enforce_batch`` accept domains in *caller* coordinates
(n, d) / (B, n, d); engines that pad internally (the Pallas backends) pad the
domain per call and un-pad the result, so callers never see padded shapes.

Padding contract (DESIGN.md §2): padded variables are unconstrained with a
non-empty domain ({value 0}), so they never change, never violate, and never
trip the wipeout check; padded values are absent from every domain and allowed
by no constraint. The AC closure over the original (n, d) slice is unchanged.
This module is the only place that implements that contract.
"""

from __future__ import annotations

import abc
import bisect
import functools
import warnings
from typing import Any, Callable, ClassVar, Dict, List, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults, obs

from .csp import CSP
from .rtac import EnforceResult

Array = jax.Array
Changed = Optional[Union[Array, np.ndarray]]


# ---------------------------------------------------------------------------
# Padding contract — the ONE implementation (kernels and engines import these)
# ---------------------------------------------------------------------------


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def next_pow2(x: int) -> int:
    """The next power of two ≥ x (x ≥ 1) — the ONE copy of the jit-shape
    quantization every batching layer uses (frontier rounds, child frontiers,
    admission buckets)."""
    return 1 << (x - 1).bit_length()


def pad_round_rows(arrays: Sequence[np.ndarray], r_p: int) -> List[np.ndarray]:
    """Pad each (R, ...) array to ``r_p`` rows by replicating its LAST row —
    enforcement is idempotent per element (and duplicate scatters write
    identical values), so padded rows are inert. The ONE copy of the
    round-padding idiom every dispatch path uses (the host stores and the
    device `FrontierTable`)."""
    r = arrays[0].shape[0]
    if r_p == r:
        return list(arrays)
    return [np.concatenate([a, np.repeat(a[-1:], r_p - r, axis=0)]) for a in arrays]


def padded_shape(n: int, d: int, n_block: int, d_mult: int):
    """The kernel-tile shape a network of caller shape (n, d) pads to. The ONE
    place the formula lives — engines that size slot tables without a CSP in
    hand (`_open_stacked_slot_pool`) agree with `kernels.ops.prepare_network`
    by construction, not by convention."""
    return round_up(max(n, n_block), n_block), round_up(d, d_mult)


def _pad_to(a, shape):
    """Zero-pad ``a`` at the end of each axis to ``shape``: with numpy for a
    host array (it stays on the host), with jax.numpy otherwise."""
    if isinstance(a, np.ndarray):
        out = np.zeros(shape, a.dtype)
        out[tuple(slice(0, k) for k in a.shape)] = a
        return out
    return jnp.pad(a, [(0, s - k) for k, s in zip(a.shape, shape)])


def pad_pairs(cons, mask, n_p: int, d_p: int):
    """Pad a *network* — (n, n, d, d) cons and (n, n) mask — to (n_p, d_p).

    Padded pairs are unconstrained (mask False, cons zero blocks) so they
    never produce a violation. Host arrays pad on the host; device arrays and
    tracers (inside a compiled install or encode) pad with jax.numpy."""
    return _pad_to(cons, (n_p, n_p, d_p, d_p)), _pad_to(mask, (n_p, n_p))


def pad_dom(dom: Array, n_p: int, d_p: int) -> Array:
    """Pad a domain tensor (..., n, d) -> (..., n_p, d_p).

    Padded variables get the singleton domain {0} (never empty → never trips
    the wipeout check); padded values are False everywhere. A host array
    pads on the host and stays numpy.
    """
    *batch, n, d = dom.shape
    if isinstance(dom, np.ndarray):
        out = _pad_to(dom, (*batch, n_p, d_p))
        out[..., n:, 0] = True
        return out
    dom = jnp.pad(dom, [(0, 0)] * len(batch) + [(0, 0), (0, d_p - d)])
    pad_rows = jnp.zeros((*batch, n_p - n, d_p), jnp.bool_).at[..., :, 0].set(True)
    return jnp.concatenate([dom, pad_rows], axis=-2)


def pad_csp_to(csp: CSP, n_p: int, d_p: int) -> CSP:
    """Pad a whole CSP — network and domain — to (n_p, d_p) under the §2
    contract. A CSP of host arrays comes back as host arrays."""
    if tuple(csp.dom.shape) == (n_p, d_p):
        return csp
    cons, mask = pad_pairs(csp.cons, csp.mask, n_p, d_p)
    return CSP(cons=cons, mask=mask, dom=pad_dom(csp.dom, n_p, d_p))


def pad_changed(changed0: Changed, n: int, n_p: int, batch: tuple = ()) -> Array:
    """Normalize+pad a changed seed (..., n) -> (..., n_p); None = all-changed.
    Padded variables are never marked changed (their domains never shrink)."""
    if changed0 is None:
        changed0 = jnp.ones((*batch, n), jnp.bool_)
    changed0 = jnp.asarray(changed0, dtype=jnp.bool_)
    return jnp.pad(changed0, [(0, 0)] * len(batch) + [(0, n_p - n)])


def as_changed(changed0: Changed) -> Optional[Array]:
    """Normalize a caller-supplied changed seed to a jax bool array (or None)."""
    if changed0 is None:
        return None
    return jnp.asarray(changed0, dtype=jnp.bool_)


# ---------------------------------------------------------------------------
# PreparedNetwork + Engine
# ---------------------------------------------------------------------------


class PreparedNetwork:
    """A CSP's constraint network compiled into one backend's resident form.

    Holds the engine that built it, the source CSP (for shapes and the root
    domain), and an opaque ``payload`` owned by the backend (padded/bitpacked
    tensors, revise closures, sharded jitted functions, host-side adjacency —
    whatever the backend's hot path needs so it never touches the raw CSP
    again).
    """

    __slots__ = ("engine", "csp", "payload")

    def __init__(self, engine: "Engine", csp: CSP, payload: Any):
        self.engine = engine
        self.csp = csp
        self.payload = payload

    @property
    def n_vars(self) -> int:
        return self.csp.dom.shape[0]

    @property
    def dom_size(self) -> int:
        return self.csp.dom.shape[1]

    def enforce(self, dom=None, changed0: Changed = None) -> EnforceResult:
        """Enforce AC on one domain (n, d); ``dom=None`` uses the CSP's root
        domain. ``changed0`` seeds the revision set (None = all variables)."""
        if dom is None:
            dom = self.csp.dom
        return self.engine.enforce(self, dom, changed0)

    def enforce_batch(self, doms, changed0: Changed = None) -> EnforceResult:
        """Enforce AC on B domains (B, n, d) in one dispatch; result fields
        carry a leading batch axis."""
        return self.engine.enforce_batch(self, doms, changed0)


class PreparedMany:
    """B constraint networks sharing (n, d), compiled into one backend's
    *stacked* resident form (DESIGN.md §6).

    Where `PreparedNetwork` amortizes preparation across the many enforcements
    of ONE search, `PreparedMany` amortizes the device across MANY independent
    instances: ``enforce_many`` resolves R domains, each against its own
    network, in one dispatch on backends that support it. ``payload`` is
    backend-owned — stacked tensors for the vmapped engines, a plain list of
    per-instance `PreparedNetwork`s for the generic fallback.
    """

    __slots__ = ("engine", "csps", "payload")

    def __init__(self, engine: "Engine", csps: Sequence[CSP], payload: Any):
        self.engine = engine
        self.csps = list(csps)
        self.payload = payload

    @property
    def n_instances(self) -> int:
        return len(self.csps)

    @property
    def n_vars(self) -> int:
        return self.csps[0].dom.shape[0]

    @property
    def dom_size(self) -> int:
        return self.csps[0].dom.shape[1]

    def enforce_many(
        self, doms, changed0: Changed = None, instance_idx=None
    ) -> EnforceResult:
        """Enforce AC on R domains (R, n, d), row i against the network of
        instance ``instance_idx[i]`` (default: ``arange(B)``, requiring R == B).
        Result fields carry the leading R axis."""
        return self.engine.enforce_many(self, doms, changed0, instance_idx)


def route_rows_on_host(enforce_row, doms, changed0: Changed, idx) -> EnforceResult:
    """The generic host-routing dispatch shared by `Engine.enforce_many` and
    `SlotPool.enforce_rows`: row i goes through ``enforce_row(idx[i], dom_i,
    changed_i)`` and the per-row results are stacked into one EnforceResult."""
    results = [
        enforce_row(int(j), doms[i], None if changed0 is None else changed0[i])
        for i, j in enumerate(idx)
    ]
    return EnforceResult(
        dom=np.stack([np.asarray(r.dom) for r in results]),
        consistent=np.asarray([bool(r.consistent) for r in results]),
        n_recurrences=np.asarray([int(r.n_recurrences) for r in results]),
    )


class SlotPool:
    """An *open-world* `PreparedMany`: a fixed-capacity table of resident
    network slots that searches join and leave mid-flight (DESIGN.md §7).

    Where `PreparedMany` stacks a closed batch of networks once, a `SlotPool`
    is the continuous-batching substrate of `repro.service`: ``install``
    compiles one network into a slot (the only O(n²d²) step, paid once per
    distinct network), ``enforce_rows`` resolves R domains — row i against
    slot ``slot_idx[i]`` — and ``release`` frees a slot for reuse when its
    last in-flight search retires. All slots share one (n_vars, dom_size)
    bucket shape, so every round reuses the same jitted program.

    This generic implementation keeps one `PreparedNetwork` per slot and
    routes rows on the host (works for every engine, including AC3). Engines
    that advertise ``slot_table = True`` get a device-resident `StackedSlotPool`
    instead — stacked tables, donated slot installs, one gather+fixpoint
    dispatch per round (`repro.engines.einsum`, `repro.engines.pallas`).
    """

    stacked: ClassVar[bool] = False

    def __init__(self, engine: "Engine", n_vars: int, dom_size: int, capacity: int):
        if capacity < 1:
            raise ValueError("SlotPool needs capacity >= 1")
        self.engine = engine
        self.n_vars = n_vars
        self.dom_size = dom_size
        self._nets: List[Optional[PreparedNetwork]] = [None] * capacity

    @property
    def capacity(self) -> int:
        return len(self._nets)

    def _check(self, slot: int, installing: bool) -> None:
        if not 0 <= slot < self.capacity:
            raise ValueError(f"slot {slot} out of range [0, {self.capacity})")
        if installing and self._nets[slot] is not None:
            raise ValueError(f"slot {slot} already installed; release it first")

    def install(self, slot: int, csp: CSP) -> None:
        """Compile ``csp``'s network into ``slot``. The CSP may be smaller than
        the pool's bucket shape: it is padded under the §2 contract on its
        way in (on the host here, inside the install program on a
        `StackedSlotPool`)."""
        self._check(slot, installing=True)
        n, d = csp.dom.shape
        if n > self.n_vars or d > self.dom_size:
            raise ValueError(
                f"install: csp shape ({n}, {d}) does not fit pool bucket "
                f"({self.n_vars}, {self.dom_size})"
            )
        faults.inject("slot.install", slot=slot)
        # the service's one O(n²d²) admission step — worth its own span
        with obs.span("slot.install", cat="engine", slot=slot,
                      n=self.n_vars, d=self.dom_size):
            self._nets[slot] = self._prepare_slot(slot, csp)
        obs.REGISTRY.counter_add("slots.installed")

    def _prepare_slot(self, slot: int, csp: CSP):
        """Backend hook: build the slot's resident form. The generic pool keeps
        a `PreparedNetwork`; stacked pools write device tensors and return a
        truthy sentinel."""
        return self.engine.prepare(pad_csp_to(csp, self.n_vars, self.dom_size))

    def release(self, slot: int) -> None:
        """Free a slot (its network may be overwritten by a later install)."""
        self._check(slot, installing=False)
        self._nets[slot] = None

    def grow(self, capacity: int) -> None:
        """Enlarge the table (amortized doubling in the service layer)."""
        if capacity < self.capacity:
            raise ValueError("SlotPool.grow cannot shrink")
        self._nets.extend([None] * (capacity - self.capacity))

    def enforce_rows(self, doms, changed0: Changed = None, slot_idx=None):
        """Enforce R domains (R, n, d), row i against slot ``slot_idx[i]``."""
        doms = np.asarray(doms)
        idx = resolve_instance_idx(slot_idx, self.capacity, doms.shape[0])

        def enforce_row(j, dom, ch):
            net = self._nets[j]
            if net is None:
                raise ValueError(f"enforce_rows: slot {j} is empty")
            return net.enforce(dom, ch)

        return route_rows_on_host(enforce_row, doms, changed0, idx)

    @property
    def resident_nbytes(self) -> int:
        """Device bytes this pool's resident networks occupy, in the engine's
        OWN representation (`Engine.network_nbytes`) — packed words for the
        bitpacked backend, not logical cons bytes."""
        occupied = sum(net is not None for net in self._nets)
        return occupied * self.engine.network_nbytes(self.n_vars, self.dom_size)


@functools.lru_cache(maxsize=None)
def slot_install_program(encode: Callable, *args) -> Callable:
    """The install program of a stacked slot table: ``install(tables, slot,
    cons, mask) -> tables`` runs the traceable ``encode(cons, mask, *args)``
    — pad the network to the table's shape and lay it out as one slot row
    per table — and writes each row into ``slot``, all in ONE compiled
    dispatch. ``cons``/``mask`` are the network as the CSP holds it (host
    arrays are uploaded once, as they are). The tables are donated, so the
    resident table is written in place, never copied (TPU/GPU; the CPU falls
    back to a copy). Cached on (``encode``, ``args``), so pools of one
    encoding and bucket share one program, compiled once per network shape."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def install(tables, slot, cons, mask):
        row = encode(cons, mask, *args)
        return jax.tree_util.tree_map(lambda t, v: t.at[slot].set(v), tables, row)

    return install


class StackedSlotPool(SlotPool):
    """A device-resident `SlotPool`: the networks live in *stacked* device
    tensors (a pytree of ``(C, ...)`` tables), an install is one donated
    compiled program that pads, encodes and writes one slot row, and
    ``enforce_rows`` is ONE dispatch that gathers each row's network from the
    tables — the open-world analogue of `PreparedMany`'s stacked dispatch
    (DESIGN.md §7).

    The backend supplies its representation as three pieces:

    - ``tables``: the initial (zeroed) slot tables — ``(C, n, n, d, d)`` bool
      cons for the einsum engines, ``(C, W, n_p, n_p·d_p)`` packed int32
      words for `pallas_packed`;
    - ``install``: the `slot_install_program` of its encoding at this
      bucket shape (the only O(n²d²) step, paid once per distinct network);
    - ``dispatch(tables, doms, changed0, idx)``: the jitted gather + fixpoint
      over the whole round.
    """

    stacked: ClassVar[bool] = True

    def __init__(
        self,
        engine: "Engine",
        n_vars: int,
        dom_size: int,
        capacity: int,
        tables,
        install: Callable,
        dispatch,
    ):
        super().__init__(engine, n_vars, dom_size, capacity)
        self._tables = tables
        self._install = install
        self._dispatch = dispatch

    def _prepare_slot(self, slot: int, csp: CSP):
        # the network's one upload: only host arrays move, as the CSP holds them
        uploaded = sum(a.nbytes for a in (csp.cons, csp.mask) if isinstance(a, np.ndarray))
        with warnings.catch_warnings():
            # CPU backends can't honour donation; the copy fallback is correct.
            warnings.filterwarnings("ignore", message=".*[Dd]onat.*")
            self._tables = self._install(self._tables, np.int32(slot), csp.cons, csp.mask)
        obs.REGISTRY.counter_add("slots.install_h2d_bytes", uploaded)
        return True  # occupancy sentinel; the network lives in the tables

    def grow(self, capacity: int) -> None:
        old = self.capacity
        super().grow(capacity)
        if capacity > old:
            self._tables = jax.tree_util.tree_map(
                lambda t: jnp.pad(
                    t, [(0, capacity - old)] + [(0, 0)] * (t.ndim - 1)
                ),
                self._tables,
            )

    def require_installed(self, slot_idx) -> None:
        """Fail loudly if any routed slot has no resident network (also the
        `FrontierTable` round's ``check_net`` hook in the service)."""
        for j in np.unique(np.asarray(slot_idx)):
            if self._nets[int(j)] is None:
                raise ValueError(f"enforce_rows: slot {int(j)} is empty")

    def enforce_rows(self, doms, changed0: Changed = None, slot_idx=None):
        idx = resolve_instance_idx(slot_idx, self.capacity, np.shape(doms)[0])
        self.require_installed(idx)
        return self._dispatch(self._tables, doms, changed0, idx)

    @property
    def tables(self):
        """The live stacked slot tables — what a `FrontierTable` round reads
        its networks from (re-read every dispatch, so installs and growth
        between rounds are picked up)."""
        return self._tables

    @property
    def resident_nbytes(self) -> int:
        """The actual footprint of the resident slot tables (all slots — the
        table is allocated whole, occupied or not)."""
        return sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(self._tables)
        )


# ---------------------------------------------------------------------------
# FrontierTable — device-resident search frontiers (DESIGN.md §8)
# ---------------------------------------------------------------------------


class FrontierRow(NamedTuple):
    """One row of a frontier dispatch: create (and enforce) the child of
    ``parent`` obtained by assigning ``var := val``; ``var < 0`` marks a root
    row — ``parent`` already holds the root domain and is enforced in place.
    ``assigned`` is the (n,) bool assignment mask of the *child* (the state its
    own MRV selection must see); ``net`` routes the row to its constraint
    network (a `PreparedMany` instance index or a `SlotPool` slot)."""

    key: Any
    parent: int
    var: int
    val: int
    assigned: np.ndarray
    net: int


class RoundMeta(NamedTuple):
    """What a frontier round ships back to the host: O(R·d) metadata, never an
    (R, n, d) domain tensor. Domain sizes never ship at all — the on-device
    MRV reduction consumes them where they live. ``handles[i]`` is row i's
    closure handle (None where inconsistent — the row was freed);
    ``branch_var``/``value_row`` are the MRV decision (garbage, and ignored,
    for inconsistent or fully-assigned rows)."""

    handles: List[Optional[int]]
    consistent: np.ndarray  # (R,) bool
    k: np.ndarray  # (R,) int32 — per-row recurrence counts
    branch_var: np.ndarray  # (R,) int32
    value_row: np.ndarray  # (R, d) bool — the branching variable's domain row
    #: kernel launches this round's enforcement cost: 1 on a fused in-kernel
    #: fixpoint, the round's max recurrence depth on the stepped while_loop
    launches: int = 1
    #: anti-MRV decision (portfolio heuristic diversity, DESIGN.md §9): the
    #: argmax counterpart of ``branch_var``/``value_row``. ``None`` unless the
    #: store was asked for it (`FrontierTable.enable_alt`) — the extra O(R·d)
    #: metadata only ships when some admitted member actually branches anti.
    alt_var: Optional[np.ndarray] = None  # (R,) int32
    alt_row: Optional[np.ndarray] = None  # (R, d) bool


_INT32_MAX = np.iinfo(np.int32).max


@functools.partial(
    jax.jit, donate_argnums=(0, 1), static_argnames=("fix", "want_alt")
)
def _frontier_step(buf, abuf, networks, parent, var, val, dest, net_idx, *, fix,
                   want_alt=False):
    """ONE fused round: gather parent closures AND assignment masks from the
    resident frontier planes, assign + enforce (the engine's fused ``fix``),
    scatter the children back, and reduce the per-row metadata — neither
    domains nor assignment masks ever leave the device. ``buf``/``abuf`` are
    donated: XLA updates the tables in place. ``want_alt`` additionally
    reduces the anti-MRV decision (portfolio heuristic diversity) — a second
    O(R·d) metadata pair, compiled in only when some search branches anti."""
    doms = buf[parent]  # (R, n, d)
    res = fix(networks, doms, var, val, net_idx)
    buf = buf.at[dest].set(res.dom)
    # the child's assignment mask: parent's mask plus the assigned variable
    # (root rows, var < 0, inherit the parent mask unchanged) — maintained on
    # device, bit-identical to the coroutine's host-side bookkeeping
    n = buf.shape[1]
    one_hot = (jnp.arange(n, dtype=var.dtype)[None, :] == jnp.maximum(var, 0)[:, None])
    assigned = abuf[parent] | (one_hot & (var >= 0)[:, None])  # (R, n)
    abuf = abuf.at[dest].set(assigned)
    # MRV on device — identical to search._select_var: first argmin over
    # unassigned domain sizes (assigned variables hidden behind a sentinel).
    # The sizes are consumed HERE; they are never shipped to the host.
    sizes = jnp.sum(res.dom, axis=-1).astype(jnp.int32)  # (R, n)
    bvar = jnp.argmin(jnp.where(assigned, _INT32_MAX, sizes), axis=-1).astype(jnp.int32)
    vrow = jnp.take_along_axis(res.dom, bvar[:, None, None], axis=1)[:, 0, :]  # (R, d)
    out = (buf, abuf, res.consistent, res.n_recurrences, bvar, vrow)
    if want_alt:
        # anti-MRV: first argmax over unassigned domain sizes — identical
        # ints + ties to search._select_var_anti (assigned → -1 sentinel)
        avar = jnp.argmax(
            jnp.where(assigned, jnp.int32(-1), sizes), axis=-1
        ).astype(jnp.int32)
        arow = jnp.take_along_axis(res.dom, avar[:, None, None], axis=1)[:, 0, :]
        out = out + (avar, arow)
    if res.revised is not None:
        # a fixpoint that streamed its network: per-row work counts, shipped
        # with the round's metadata
        out = out + (res.revised, res.streamed)
    return out


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _root_write(buf, abuf, row, dom, assigned):
    """Donated single-row install (root domain + assignment-mask upload)."""
    return buf.at[row].set(dom), abuf.at[row].set(assigned)


@jax.jit
def _row_read(buf, row):
    """One-row gather (solution extraction) — jitted so the row index rides
    as a device scalar instead of an implicit eager-slice transfer."""
    return buf[row]


def _buffer_zeros(shape):
    """A zeroed device buffer. Allocation is not data motion: the fill value
    is a scalar constant, so it is exempted from the transfer audit the
    frontier runs under (`jax.transfer_guard("disallow")` stays clean)."""
    with jax.transfer_guard("allow"):
        return jnp.zeros(shape, jnp.bool_)


class _PendingFrontierRound:
    """Handle for one in-flight frontier dispatch: the metadata arrays are
    still device futures (JAX async dispatch); ``resolve()`` fetches them —
    the round's only device→host transfer — and frees inconsistent rows."""

    def __init__(self, table: "FrontierTable", meta, dest: List[int], keys: List[Any], r: int,
                 alt: bool = False):
        self._table = table
        self._meta = meta
        self._dest = dest
        self._keys = keys
        self._r = r
        self._alt = alt  # whether the anti-MRV pair follows the MRV pair

    def resolve(self) -> RoundMeta:
        # the host's one wait on the device in a round
        with obs.span("round.wait", cat="driver"):
            cons, k, bvar, vrow, *rest = jax.device_get(self._meta)
        self._table._count_d2h(cons, k, bvar, vrow, *rest)
        r = self._r
        alt, counts = (rest[:2], rest[2:]) if self._alt else ((), rest)
        if counts:  # the round's fixpoint streamed its networks from HBM
            revised, streamed = counts
            obs.REGISTRY.counter_add("kernel.revised_vars", int(np.sum(revised[:r])))
            obs.REGISTRY.counter_add(
                "kernel.stream_bytes", int(np.sum(streamed[:r], dtype=np.float64))
            )
        handles: List[Optional[int]] = []
        for i, (key, row) in enumerate(zip(self._keys, self._dest)):
            if bool(cons[i]):
                handles.append(row)
            else:  # a wiped-out child is never revisited — free its row now
                self._table.free(key, row)
                handles.append(None)
        # the round's launch bill: a fused fixpoint is ONE kernel regardless
        # of recurrence depth; the stepped path launched one revise per
        # iteration of the deepest row (XLA while_loop runs to the max k)
        launches = 1 if self._table.fused_fixpoint else max(1, int(k[:r].max()))
        self._table.launches += launches
        avar, arow = (alt[0][:r], alt[1][:r]) if alt else (None, None)
        return RoundMeta(
            handles, cons[:r], k[:r], bvar[:r], vrow[:r], launches, avar, arow
        )


class FrontierTable:
    """Device-resident search frontiers (DESIGN.md §8): a donated
    ``(R_cap, n, d)`` buffer holding every live search node's AC closure for
    the life of the search, plus the fused round dispatch over it.

    The host never touches domains: ``begin`` uploads one root domain per
    admitted search (the only O(n·d) host→device transfer a search ever
    makes), ``dispatch`` launches the fused gather→assign→enforce→scatter→
    reduce step (`_frontier_step`) whose host traffic is O(R·d) metadata
    both ways, and ``extract`` fetches one closure exactly once, at solution
    extraction. Rows are owned per search key: ``free`` returns a single row
    (dead branch), ``release`` reclaims everything a retired search held.
    Capacity grows by doubling (a device-side pad; O(log) reallocations).

    All host↔device traffic is *explicit* (`jax.device_put`/`device_get`) and
    metered — ``jax.transfer_guard("disallow")`` passes over a whole lockstep
    run, which is exactly what `tests/test_frontier.py` asserts — and the
    cumulative byte counters feed the ``frontier`` benchmark section.
    """

    pipelined: ClassVar[bool] = True

    def __init__(
        self,
        n_vars: int,
        dom_size: int,
        networks: Callable[[], Any],
        fix: Callable,
        capacity: int = 64,
        pad_rounds: bool = True,
        check_net: Optional[Callable] = None,
        fused_fixpoint: bool = False,
    ):
        if capacity < 2:
            raise ValueError("FrontierTable needs capacity >= 2")
        #: optional per-round validation of the row→network routing (the
        #: service passes the slot pool's occupancy check, so a stale route
        #: fails loudly instead of solving against a zeroed network)
        self._check_net = check_net
        self.n_vars = n_vars
        self.dom_size = dom_size
        self._networks = networks  # () -> pytree; re-read every round, so slot
        # installs and pool growth between rounds are picked up automatically
        self._fix = fix
        self._buf = _buffer_zeros((capacity, n_vars, dom_size))
        self._abuf = _buffer_zeros((capacity, n_vars))  # assignment masks
        self._free_rows: List[int] = list(range(capacity - 1, -1, -1))
        self._rows_of: Dict[Any, set] = {}
        self._net_of: Dict[Any, int] = {}
        self._pad_rounds = pad_rounds
        # Every XLA program is shaped on the round width, so a draining tail
        # that walked back down the pow2 ladder would compile a fresh program
        # per step — the dominant cost of a cold run. Rounds therefore pad to
        # the nearest ALREADY-COMPILED width ≥ r (compiling a new pow2 width
        # only when r exceeds them all): compiles happen on the way up only,
        # and tails reuse the smallest adequate program. Padded rows replicate
        # the last real row (idempotent, no extra fixpoint iterations), so a
        # somewhat wider round costs linear width, strictly cheaper than a
        # compile.
        self._widths: List[int] = []
        #: whether ``fix`` runs the whole recurrence in one kernel launch
        #: (drives the launch accounting in `_PendingFrontierRound.resolve`)
        self.fused_fixpoint = bool(fused_fixpoint)
        # transfer telemetry (metadata bytes; root/extract counted separately)
        self.rounds = 0
        self.launches = 0  # cumulative kernel launches across rounds
        self.rows_dispatched = 0  # real rows
        self.rows_padded = 0  # rows actually shaped into the dispatches
        self.rows_pow2 = 0  # plain next-pow2 rows (the pre-§8 round widths)
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.root_bytes = 0
        self.extract_bytes = 0
        #: ship the anti-MRV metadata pair with every round (DESIGN.md §9) —
        #: off by default so the O(R·d) budget is unchanged unless some
        #: admitted portfolio member actually branches anti-MRV
        self._want_alt = False

    def enable_alt(self) -> None:
        """Opt this table into anti-MRV metadata for all subsequent rounds
        (a static jit arg — flipping it compiles fresh round programs, so the
        driver sets it once at group admission, not per round)."""
        self._want_alt = True

    @property
    def capacity(self) -> int:
        return self._buf.shape[0]

    @property
    def rows_live(self) -> int:
        return self.capacity - len(self._free_rows)

    def spare_rows(self) -> int:
        """Rows currently unoccupied — what speculative admission sizes its
        duplication budget against (capacity can still grow by doubling, but
        speculation should fill slack, not force reallocations)."""
        return len(self._free_rows)

    @property
    def host_bytes_per_round(self) -> float:
        """Mean metadata bytes (both directions) one lockstep round moves —
        the number the O(R·n·d)→O(R·d) claim is measured by."""
        return (self.h2d_bytes + self.d2h_bytes) / max(self.rounds, 1)

    @property
    def domain_bytes_per_round(self) -> float:
        """The counterfactual: what the pre-§8 protocol moved per round — the
        full (R, n, d) bool domains, host→device and back, at the plain
        next-pow2 round widths it actually padded to (NOT this table's
        ratcheted widths — the comparison stays honest)."""
        return 2.0 * self.rows_pow2 * self.n_vars * self.dom_size / max(self.rounds, 1)

    def _count_d2h(self, *arrays) -> None:
        nbytes = sum(np.asarray(a).nbytes for a in arrays)
        self.d2h_bytes += nbytes
        obs.REGISTRY.counter_add("frontier.d2h_bytes", nbytes)

    def _alloc(self, key) -> int:
        if not self._free_rows:
            old = self.capacity
            # doubling is an on-device allocation, not data motion (the pad
            # fill is a scalar constant) — exempt from the transfer audit
            with jax.transfer_guard("allow"):
                self._buf = jnp.pad(self._buf, ((0, old), (0, 0), (0, 0)))
                self._abuf = jnp.pad(self._abuf, ((0, old), (0, 0)))
            self._free_rows.extend(range(2 * old - 1, old - 1, -1))
        row = self._free_rows.pop()
        self._rows_of[key].add(row)
        return row

    # --- search lifecycle ---------------------------------------------------

    def register(self, key, net: int) -> None:
        """Register a search key with its network routing but NO root upload —
        how a split sibling joins the table: its first frontier row is a
        child-create against the parent's still-resident row, so the sibling
        never moves a domain across the host boundary at all."""
        if key in self._rows_of:
            raise ValueError(f"search key {key!r} already registered")
        self._rows_of[key] = set()
        self._net_of[key] = int(net)

    def begin(self, key, net: int, root_dom: np.ndarray, assigned=None) -> int:
        """Register a search and upload its root domain + initial assignment
        mask into a fresh row — the ONE domain-sized host→device transfer of
        the search's lifetime (``assigned`` marks bucket-padding variables as
        born assigned; the mask lives on device from here on)."""
        self.register(key, net)
        row = self._alloc(key)
        dom = jax.device_put(np.asarray(root_dom, dtype=bool))
        if assigned is None:
            assigned = np.zeros((self.n_vars,), dtype=bool)
        mask = jax.device_put(np.asarray(assigned, dtype=bool))
        self.root_bytes += int(dom.nbytes) + int(mask.nbytes)
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=".*[Dd]onat.*")
            self._buf, self._abuf = _root_write(
                self._buf, self._abuf, jax.device_put(np.int32(row)), dom, mask
            )
        return row

    def free(self, key, row: int) -> None:
        """Return one row (a dead branch) to the free list."""
        rows = self._rows_of.get(key)
        if rows is not None and row in rows:
            rows.discard(row)
            self._free_rows.append(row)

    def release(self, key) -> None:
        """Reclaim every row a retired search still holds."""
        self._free_rows.extend(self._rows_of.pop(key, ()))
        self._net_of.pop(key, None)

    def extract(self, key, row: int) -> np.ndarray:
        """Fetch one closure — exactly once per search, at solution
        extraction (an explicit device→host transfer)."""
        dom = np.asarray(
            jax.device_get(_row_read(self._buf, jax.device_put(np.int32(row))))
        )
        self.extract_bytes += int(dom.nbytes)
        return dom

    # --- the fused round ----------------------------------------------------

    def dispatch(self, specs: Sequence[FrontierRow], net_idx=None) -> _PendingFrontierRound:
        """Launch one fused round over ``specs`` (JAX async — returns
        immediately; ``resolve()`` on the result blocks on the metadata).
        ``net_idx`` optionally supplies the per-row network routing (the
        driver's cached array); default derives it from the specs."""
        r = len(specs)
        if r == 0:
            raise ValueError("dispatch needs at least one row")
        # before _alloc/_check_net so a fired fault leaves the table unmutated
        faults.inject("frontier.step", rows=r)
        if self._check_net is not None:
            self._check_net(
                net_idx
                if net_idx is not None
                else np.fromiter((self._net_of[s.key] for s in specs), np.int32, r)
            )
        dest = [s.parent if s.var < 0 else self._alloc(s.key) for s in specs]
        parent = np.fromiter((s.parent for s in specs), np.int32, r)
        var = np.fromiter((s.var for s in specs), np.int32, r)
        val = np.fromiter((s.val for s in specs), np.int32, r)
        if net_idx is None:
            net_idx = np.fromiter((self._net_of[s.key] for s in specs), np.int32, r)
        dest_arr = np.asarray(dest, np.int32)
        if self._pad_rounds:
            r_p = next((w for w in self._widths if w >= r), None)
            if r_p is None:  # wider than anything compiled: a new pow2 width
                r_p = next_pow2(r)
                bisect.insort(self._widths, r_p)
        else:
            r_p = r
        # replicate the LAST row verbatim (dest included): identical inputs
        # write identical values, so the duplicate scatter is harmless and
        # the jitted step reuses already-compiled widths
        args = tuple(
            jax.device_put(a)
            for a in pad_round_rows(
                (parent, var, val, dest_arr, np.asarray(net_idx, np.int32)), r_p
            )
        )
        h2d = sum(int(a.nbytes) for a in args)
        self.h2d_bytes += h2d
        obs.REGISTRY.counter_add("frontier.h2d_bytes", h2d)
        self.rounds += 1
        self.rows_dispatched += r
        self.rows_padded += r_p
        self.rows_pow2 += next_pow2(r)
        # the launch span brackets the dispatch call: under the default async
        # timing it measures launch-side cost only; under timing="fenced" the
        # fence blocks on the round's metadata, so the span is the device
        # round itself (block_until_ready moves no data — the transfer-guard
        # audit stays clean, and verdicts are bit-identical either way)
        with obs.span("kernel.launch", cat="kernel", rows=r, padded=r_p,
                      fused=self.fused_fixpoint):
            faults.inject("kernel.launch", rows=r)
            with warnings.catch_warnings():
                warnings.filterwarnings("ignore", message=".*[Dd]onat.*")
                self._buf, self._abuf, *meta = _frontier_step(
                    self._buf, self._abuf, self._networks(), *args, fix=self._fix,
                    want_alt=self._want_alt,
                )
            obs.fence(meta)
        obs.REGISTRY.gauge_set("frontier.rows_live", self.rows_live)
        obs.REGISTRY.gauge_set("frontier.capacity", self.capacity)
        return _PendingFrontierRound(self, tuple(meta), dest, [s.key for s in specs], r,
                                     alt=self._want_alt)


def frontier_capacity(n_searches: int, n_vars: int, dom_size: int,
                      cap: int = 8192) -> int:
    """Initial `FrontierTable` rows for ``n_searches`` concurrent searches of
    shape (n_vars, dom_size). A DFS level holds its node plus the unvisited
    sibling closures, so ~(n + d) rows per search bounds the common case;
    rows are n·d bools, so presizing is cheap while mid-run growth recompiles
    the fused step for every live round shape. Growth still works — this is a
    sizing heuristic, not a limit."""
    return max(64, min(cap, next_pow2(n_searches * (n_vars + dom_size + 2))))


def resolve_instance_idx(instance_idx, n_instances: int, n_rows: int) -> np.ndarray:
    """Normalize/validate the row→instance map of ``enforce_many``."""
    if instance_idx is None:
        if n_rows != n_instances:
            raise ValueError(
                f"enforce_many got {n_rows} domains for {n_instances} instances; "
                "pass instance_idx to map rows to instances"
            )
        return np.arange(n_instances, dtype=np.int32)
    idx = np.asarray(instance_idx, dtype=np.int32)
    if idx.shape != (n_rows,):
        raise ValueError(f"instance_idx shape {idx.shape} != ({n_rows},)")
    if idx.size and (idx.min() < 0 or idx.max() >= n_instances):
        raise ValueError(f"instance_idx out of range [0, {n_instances})")
    return idx


class Engine(abc.ABC):
    """One enforcement backend. Register concrete engines in `repro.engines`."""

    #: registry key (and the string accepted by ``mac_solve(engine=...)``)
    name: ClassVar[str]
    #: unit of ``EnforceResult.n_recurrences`` — "recurrences" for the tensor
    #: fixpoint backends (Table 1 #Recurrence), "revisions" for AC3
    #: (Table 1 #Revision). `SearchStats` files counts accordingly.
    count_unit: ClassVar[str] = "recurrences"
    #: whether ``enforce_batch`` is genuinely one parallel dispatch. Sequential
    #: host engines (AC3) set this False so MAC search enforces children
    #: lazily one at a time — eager batching would do strictly more work there
    #: and skew the per-assignment statistics.
    supports_batch: ClassVar[bool] = True
    #: whether ``enforce_many`` is one stacked device dispatch (jit-shaped on
    #: the row count, so callers benefit from padding rounds to reused shapes).
    #: False = the generic host-routing fallback, where padded rows would be
    #: real enforcement work thrown away.
    stacked_many: ClassVar[bool] = False
    #: whether ``open_slot_pool`` is backed by a device-resident stacked slot
    #: table (one gather+fixpoint dispatch per round). The service keys its
    #: per-bucket wiring (round padding, occupancy accounting) off this
    #: advertisement — engines declare the capability, callers never hardcode
    #: backend names. True requires ``_open_stacked_slot_pool``.
    slot_table: ClassVar[bool] = False
    #: whether this engine supplies the fused frontier dispatch (DESIGN.md §8):
    #: ``frontier_fix``/``frontier_networks`` back a device-resident
    #: `FrontierTable`, so lockstep rounds gather parents, assign, enforce and
    #: select on device and ship only O(R·d) metadata to the host. False =
    #: the search layer's host-side store (domains in numpy, as for AC3).
    device_frontier: ClassVar[bool] = False
    #: whether enforcement runs its whole recurrence inside ONE kernel launch
    #: (the fused in-kernel fixpoint). Engines with a runtime mode switch (the
    #: Pallas backends' ``fixpoint=`` knob) shadow this with an instance
    #: attribute; the frontier's launch accounting reads it either way.
    fused_fixpoint: ClassVar[bool] = False
    #: ceiling on how many frontier rows ONE request may speculatively occupy
    #: on this backend (tree-split siblings + portfolio members, DESIGN.md §9).
    #: An occupancy hint, not a semantic knob: wide stacked backends amortize
    #: extra rows almost for free, host loops pay per row. The service clamps
    #: its duplication budget by it at admission.
    speculative_rows_hint: ClassVar[int] = 32

    def network_nbytes(self, n_vars: int, dom_size: int) -> int:
        """Resident device bytes of ONE prepared network of caller shape
        (n_vars, dom_size) in THIS engine's representation — the unit the
        service's cache budget counts. The generic answer is the logical bool
        network (cons n²d² + mask n², one byte per element); engines with a
        padded or packed resident form (the Pallas backends) override with
        their true footprint, e.g. packed u32 words at 8× fewer bytes."""
        return n_vars * n_vars * dom_size * dom_size + n_vars * n_vars

    def prepare(self, csp: CSP) -> PreparedNetwork:
        """Compile the constraint network into this backend's resident form.
        Called once per CSP; everything O(n²d²) happens here."""
        return PreparedNetwork(self, csp, self._prepare_payload(csp))

    @abc.abstractmethod
    def _prepare_payload(self, csp: CSP) -> Any:
        ...

    @abc.abstractmethod
    def enforce(self, prepared: PreparedNetwork, dom, changed0: Changed = None) -> EnforceResult:
        ...

    def enforce_batch(self, prepared: PreparedNetwork, doms, changed0: Changed = None) -> EnforceResult:
        """Generic fallback: loop on the host and stack. Device backends
        override this with a single vmapped/sharded dispatch."""
        results = [
            self.enforce(prepared, doms[i], None if changed0 is None else changed0[i])
            for i in range(len(doms))
        ]
        return EnforceResult(
            dom=np.stack([np.asarray(r.dom) for r in results]),
            consistent=np.asarray([bool(r.consistent) for r in results]),
            n_recurrences=np.asarray([int(r.n_recurrences) for r in results]),
        )

    # --- multi-instance (one workload, many independent CSPs) ---------------

    def prepare_many(self, csps: Sequence[CSP]) -> PreparedMany:
        """Compile B constraint networks sharing (n, d) into one stacked
        resident form. Everything O(B·n²d²) happens here, once per workload."""
        csps = list(csps)
        if not csps:
            raise ValueError("prepare_many needs at least one CSP")
        n, d = csps[0].dom.shape
        for i, c in enumerate(csps):
            if tuple(c.dom.shape) != (n, d):
                raise ValueError(
                    f"prepare_many: instance {i} has shape {tuple(c.dom.shape)}, "
                    f"expected ({n}, {d}) — all instances must share (n_vars, dom_size)"
                )
        return PreparedMany(self, csps, self._prepare_many_payload(csps))

    def _prepare_many_payload(self, csps: List[CSP]) -> Any:
        """Generic fallback: per-instance `PreparedNetwork`s. Vmappable
        backends override this with genuinely stacked network tensors."""
        return [self.prepare(c) for c in csps]

    def enforce_many(
        self, prepared: PreparedMany, doms, changed0: Changed = None, instance_idx=None
    ) -> EnforceResult:
        """Generic fallback: route each row to its instance's prepared network
        on the host. Vmappable backends override this with ONE device dispatch
        over the stacked networks."""
        doms = np.asarray(doms)
        idx = resolve_instance_idx(instance_idx, prepared.n_instances, doms.shape[0])
        nets: List[PreparedNetwork] = prepared.payload
        return route_rows_on_host(
            lambda j, dom, ch: self.enforce(nets[j], dom, ch), doms, changed0, idx
        )

    # --- device-resident frontiers (DESIGN.md §8) ---------------------------

    def frontier_fix(self) -> Callable:
        """The fused assign+enforce core a `FrontierTable` round jits over:
        a *traceable* ``fix(networks, doms, var, val, net_idx)`` →
        `EnforceResult` applying the batched Alg. 2 assignment (``var < 0`` =
        root row, no assignment, all-changed seed) and the stacked fixpoint.
        MUST return a stable function object across calls — it keys the
        frontier step's jit cache."""
        raise NotImplementedError(
            f"{type(self).__name__} advertises device_frontier="
            f"{self.device_frontier} and does not implement frontier_fix"
        )

    def frontier_networks(self, prepared: PreparedMany) -> Any:
        """The jax pytree of stacked networks ``frontier_fix`` consumes, for a
        closed `prepare_many` workload (the open-world analogue is
        `StackedSlotPool.tables`)."""
        raise NotImplementedError

    def open_frontier(self, networks: Callable[[], Any], n_vars: int,
                      dom_size: int, capacity: int = 64,
                      check_net: Optional[Callable] = None) -> "FrontierTable":
        """A device-resident `FrontierTable` over this engine's fused frontier
        dispatch. ``networks`` is a zero-arg callable returning the live
        stacked-network pytree (re-read every round); ``check_net`` optionally
        validates each round's row→network routing (e.g. slot occupancy)."""
        return FrontierTable(n_vars, dom_size, networks, self.frontier_fix(),
                             capacity=capacity, check_net=check_net,
                             fused_fixpoint=self.fused_fixpoint)

    # --- open-world slots (continuous batching, DESIGN.md §7) ---------------

    def open_slot_pool(self, n_vars: int, dom_size: int, capacity: int) -> SlotPool:
        """A `SlotPool` of ``capacity`` resident network slots sharing one
        (n_vars, dom_size) bucket shape. Routed by the ``slot_table``
        advertisement: stacked engines get their device-resident table
        (`_open_stacked_slot_pool`), everything else the generic host-routing
        pool."""
        if self.slot_table:
            return self._open_stacked_slot_pool(n_vars, dom_size, capacity)
        return SlotPool(self, n_vars, dom_size, capacity)

    def _open_stacked_slot_pool(
        self, n_vars: int, dom_size: int, capacity: int
    ) -> StackedSlotPool:
        """Backend hook for ``slot_table = True`` engines: build the
        device-resident stacked pool (tables + encode + round dispatch)."""
        raise NotImplementedError(
            f"{type(self).__name__} advertises slot_table=True but does not "
            "implement _open_stacked_slot_pool"
        )

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"<{type(self).__name__} name={self.name!r}>"
