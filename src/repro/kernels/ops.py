"""Jitted wrappers binding the Pallas kernels into the RTAC fixpoint.

Handles the shape contract between the algorithm (n vars × d values, any sizes)
and the kernels (padded, flattened, optionally bitpacked). The padding contract
itself lives in `repro.core.engine` (DESIGN.md §2) — this module only lays the
padded tensors out the way `rtac_support` reads them:

- one set of closure factories, keyed on the encoding (``"dense"`` int8 or
  ``"packed"`` int32 words) and the interpret mode. They are ``lru_cache``-d,
  so each returned function object is stable and keys the jit caches; kernel
  dims are read from the traced shapes, so one closure serves every bucket.
- network preparation (padding + transpose + bitpack of the O(n²d²) constraint
  tensor) is memoized per CSP identity, so repeated preparation of the same
  network is free. The Engine layer (`repro.engines.pallas`) calls
  ``prepare_network`` once per CSP by construction.

Whether Pallas interprets or compiles is decided in one place,
`interpret_mode`, from the backend: the CPU interprets the kernel bodies, a
TPU compiles them. Engines read it when they are constructed and pass it to
every kernel call.
"""

from __future__ import annotations

import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults, obs
from repro.core import rtac
from repro.core.csp import CSP
from repro.core.engine import pad_dom, pad_pairs, padded_shape
from . import autotune, ref, rtac_support

Array = jax.Array

#: value-axis multiple both encodings pad d to (the one place it is set —
#: engines sizing slot tables without a CSP import this)
D_MULT = 8


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted on this process's backend: the
    CPU interprets, a TPU compiles, any other backend is refused."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas engines run on 'cpu' (interpreted) or 'tpu'; got {backend!r}")


def _count_build(name: str) -> None:
    """Registry tick for one kernel-closure construction. The factories are
    ``lru_cache``-d, so this fires once per distinct (encoding, mode) program
    family — the compiled-program census the obs CLI reports."""
    obs.counter_add("kernels.fn_builds")
    obs.counter_add(f"kernels.fn_builds.{name}")


# (kind, n_block, id(cons), id(mask)) -> (wref(cons), wref(mask), (network, dims)).
# Keyed by the identity of BOTH network tensors — the prepared form embeds the
# mask, so a CSP sharing `cons` but carrying a different `mask` must miss. The
# weakrefs guard against id() reuse after gc, and their callbacks evict the
# entry when either tensor is collected.
_NETWORK_CACHE: dict = {}


def _cached(kind: str, csp: CSP, n_block: int, build):
    key = (kind, n_block, id(csp.cons), id(csp.mask))
    hit = _NETWORK_CACHE.get(key)
    if hit is not None and hit[0]() is csp.cons and hit[1]() is csp.mask:
        return hit[2]
    value = build()
    evict = lambda _ref: _NETWORK_CACHE.pop(key, None)
    try:
        rc = weakref.ref(csp.cons, evict)
        rm = weakref.ref(csp.mask, evict)
    except TypeError:  # non-weakrefable leaf; just skip caching
        return value
    _NETWORK_CACHE[key] = (rc, rm, value)
    return value


def kernel_dims(kind: str, n: int, d: int, n_block: int = 8):
    """Kernel coordinates for caller shape (n, d): (n_p, d_p) dense,
    (n_p, d_p, W) packed."""
    n_p, d_p = padded_shape(n, d, n_block, D_MULT)
    if kind == "dense":
        return n_p, d_p
    return n_p, d_p, rtac_support.words_per_domain(d_p)


def encode_cons(kind: str, cons: Array) -> Array:
    """Padded (n, n, d, d) bool constraints -> the kernel's network tensor:
    dense (N, N) int8 with rows (y, b) and columns (x, a); packed (W, n, N)
    int32 with bit b of word w = C[x, y, a, 32w + b] at [w, y, x·d + a]."""
    n, _, d, _ = cons.shape
    if kind == "dense":
        return jnp.transpose(cons, (1, 3, 0, 2)).reshape(n * d, n * d).astype(jnp.int8)
    words = ref.pack_bits_ref(cons)  # (x, y, a, W) uint32
    w = words.shape[-1]
    words = jnp.transpose(words, (3, 1, 0, 2)).reshape(w, n, n * d)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def encode_network(cons, mask, kind: str, n_p: int, d_p: int):
    """A network as its CSP holds it — (n, n, d, d) bool cons and (n, n) mask,
    host or device — padded to kernel shape (n_p, d_p) (`pad_pairs`, the §2
    contract) and laid out as the kernel's pair (`encode_cons`, u8 mask), in
    one compiled program per shape. A slot install traces it into its donated
    write (`core.engine.slot_install_program`)."""
    cons, mask = pad_pairs(cons, mask, n_p, d_p)
    return encode_cons(kind, cons), mask.astype(jnp.uint8)


def prepare_network(kind: str, csp: CSP, n_block: int = 8):
    """-> (network, dom_padded, dims). network = (cons, mask u8 (n_p, n_p)).

    The network half is memoized per CSP; the domain is padded fresh (O(n·d))."""
    faults.inject("kernel.launch", kernel=kind)

    def build():
        n_p, d_p = padded_shape(*csp.dom.shape, n_block, D_MULT)
        network = encode_network(csp.cons, csp.mask, kind, n_p, d_p)
        return network, kernel_dims(kind, n_p, d_p, n_block)

    network, dims = _cached(kind, csp, n_block, build)
    return network, pad_dom(csp.dom, dims[0], dims[1]), dims


prepare_dense = functools.partial(prepare_network, "dense")
prepare_packed = functools.partial(prepare_network, "packed")


def kernel_operands(net_g, doms, changed):
    """Rows in padded (R, n, d) coordinates -> the kernel operands."""
    cons_g, mask_g = net_g
    r, n_p, d_p = doms.shape
    dom = doms.astype(jnp.int32).reshape(r, 1, n_p * d_p)
    seed = changed.astype(jnp.int32).reshape(r, n_p, 1)
    # mask[r, y, x·d + a] = constrained(x, y): the mask spread over x's values
    mask = jnp.repeat(jnp.swapaxes(mask_g, 1, 2), d_p, axis=2).astype(jnp.int8)
    return cons_g, dom, seed, mask


def stream_mask_tiles(mask_tables, d_p: int, ty: int):
    """Stacked (B, n_p, n_p) u8 masks -> the streamed kernel's (B, T, ty, N)
    int8 mask tiles: tile t holds mask[y, x·d + a] = constrained(x, y) for
    its variables y (`rtac_support.stream_tile_starts`), whole, so each tile
    is one aligned DMA."""
    n_p = mask_tables.shape[1]
    starts = np.asarray(rtac_support.stream_tile_starts(n_p, ty))
    rows = starts[:, None] + np.arange(ty)  # (T, ty)
    return jnp.repeat(jnp.swapaxes(mask_tables, 1, 2)[:, rows], d_p, axis=3).astype(jnp.int8)


def _fixpoint_stream(kind: str, interpret: bool, networks, doms, changed, idx):
    """The fused fixpoint at a shape no row of which fits VMEM: the stacked
    tables and the row→network index go to the kernel as they are (no
    per-row gather), and the network streams from HBM. A row equal to the
    row before it (a round padded to its width repeats its last row) is not
    run: it takes that row's result, and reads and revises nothing."""
    r, n_p, d_p = doms.shape
    ty = rtac_support.stream_ty(n_p, d_p)
    if kind != "packed" or not ty:
        raise ValueError(
            f"{kind} fixpoint at kernel shape (n={n_p}, d={d_p}): no row fits the "
            f"{rtac_support.VMEM_BUDGET / 2**20:.0f} MiB VMEM budget, and only the "
            "packed encoding streams its network from HBM"
        )
    cons, mask = networks
    idx = idx.astype(jnp.int32)
    ok = jnp.all(jnp.any(doms, axis=-1), axis=-1)  # (R,) no domain empty
    same = (idx[1:] == idx[:-1]) & jnp.all(doms[1:] == doms[:-1], axis=(1, 2)) & jnp.all(
        changed[1:] == changed[:-1], axis=1)
    same = jnp.concatenate([jnp.zeros((1,), jnp.bool_), same])
    src = jax.lax.cummax(jnp.where(same, 0, jnp.arange(r)))  # the row each one copies
    seed = changed & (ok & ~same)[:, None]
    dom, ok, k, revised, tiles = rtac_support.fixpoint_rows_stream(
        cons, stream_mask_tiles(mask, d_p, ty), idx,
        doms.astype(jnp.int32).reshape(r, 1, n_p * d_p),
        seed.astype(jnp.int32).reshape(r, n_p, 1),
        ok.astype(jnp.int32).reshape(r, 1, 1),
        d=d_p, ty=ty, interpret=interpret,
    )
    tile_bytes = rtac_support.stream_tile_bytes(n_p, d_p, ty)
    return rtac.EnforceResult(
        dom[src].reshape(r, n_p, d_p).astype(jnp.bool_),
        ok[src, 0, 0].astype(jnp.bool_),
        k[src, 0, 0],
        revised[:, 0, 0],
        # float32: a row's bytes can pass 2^31; a multiple of the tile's
        # bytes is exact far past any fixpoint's tile count
        tiles[:, 0, 0].astype(jnp.float32) * tile_bytes,
    )


# ---------------------------------------------------------------------------
# Closure factories
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def rows_fn(kind: str, interpret: bool):
    """Stacked revise-rows closure (`rtac.ReviseRowsFn`): ``net_g`` leaves
    carry a leading row axis (gathered from the slot table); returns the
    violated (R, n_p, d_p) bool of one sweep per row."""
    _count_build(f"{kind}_rows")

    def revise_rows(net_g, doms, changed):
        r, n_p, d_p = doms.shape
        block_r = autotune.effective_block_r(rtac_support.max_block_r(kind, n_p, d_p), r)
        viol = rtac_support.revise_rows(
            *kernel_operands(net_g, doms, changed),
            encoding=kind, d=d_p, block_r=block_r, interpret=interpret,
        )
        return viol.reshape(r, n_p, d_p).astype(jnp.bool_)

    return revise_rows


@functools.lru_cache(maxsize=None)
def revise_fn(kind: str, interpret: bool):
    """Single-network revise closure (`rtac.ReviseFn`): the stacked kernel
    with one row."""
    _count_build(f"{kind}_revise")
    rows = rows_fn(kind, interpret)

    def revise(net, dom, changed):
        net_g = jax.tree_util.tree_map(lambda t: t[None], net)
        return rows(net_g, dom[None], changed[None])[0]

    return revise


@functools.lru_cache(maxsize=None)
def fixpoint_rows_fn(kind: str, interpret: bool):
    """Stacked one-launch fixpoint: (networks, dom_p, ch_p, idx) ->
    EnforceResult in padded coordinates, row i against ``networks[idx[i]]``
    of the stacked tables — the signature of `rtac.enforce_rows_generic`, so
    engines swap it for the stepped path wholesale.

    Chosen by shape alone: where one row's network fits VMEM
    (`rtac_support.max_block_r` > 0) each row's network is gathered and the
    in-VMEM kernel runs; where none does, the stacked tables stream from HBM
    (`_fixpoint_stream`), and the result also carries each row's revised
    variables and streamed bytes."""
    _count_build(f"{kind}_fixpoint_rows")

    def fixpoint_rows(networks, doms, changed, idx):
        r, n_p, d_p = doms.shape
        if rtac_support.max_block_r(kind, n_p, d_p) == 0:
            return _fixpoint_stream(kind, interpret, networks, doms, changed, idx)
        net_g = jax.tree_util.tree_map(lambda t: t[idx], networks)
        block_r = autotune.fused_block_r(kind, n_p, d_p, r)
        dom, ok, k = rtac_support.fixpoint_rows(
            *kernel_operands(net_g, doms, changed),
            encoding=kind, d=d_p, block_r=block_r, interpret=interpret,
        )
        return rtac.EnforceResult(
            dom.reshape(r, n_p, d_p).astype(jnp.bool_),
            ok[:, 0, 0].astype(jnp.bool_),
            k[:, 0, 0],
        )

    return fixpoint_rows


@functools.partial(jax.jit, static_argnames=("fixpoint_rows_fn",))
def enforce_rows_fused(networks, dom, changed0, instance_idx, fixpoint_rows_fn):
    """Fused-kernel counterpart of `rtac.enforce_rows_generic`: each row
    against its own network of the stacked tables, in ONE kernel launch of
    the whole recurrence. Inputs/outputs match `enforce_rows_generic` so
    `engines.pallas` routes between them with a flag."""
    return fixpoint_rows_fn(networks, dom, changed0, instance_idx)


# ---------------------------------------------------------------------------
# Fused assign + enforce frontier entries (DESIGN.md §8)
# ---------------------------------------------------------------------------


def _padded_seed(var, n: int, n_p: int):
    """The Prop. 2 revision seed in padded coordinates: ``one_hot(var)`` for
    assigned rows, all real variables for root rows (``var < 0``); padded
    variables are never seeded (their domains never shrink). Identical to
    `pad_changed` applied to the caller-coordinate seed."""
    ar = jnp.arange(n_p, dtype=var.dtype)[None, :]
    is_root = (var < 0)[:, None]
    return jnp.where(is_root, ar < n, ar == jnp.maximum(var, 0)[:, None])


@functools.lru_cache(maxsize=None)
def frontier_fn(kind: str, fused: bool, interpret: bool):
    """Fused assign+enforce frontier dispatch: one traced program pads R
    parent closures into kernel coordinates, applies the batched Alg. 2
    assignment (`rtac_support.assign_padded_rows`), and runs the fixpoint —
    one fused launch, or the stepped while_loop around per-sweep launches.
    The device never sees a host-built domain."""
    _count_build(f"{kind}_frontier{'_fused' if fused else ''}")

    def assign_enforce_rows(net_g, doms, var, val, idx):
        _, n, d = doms.shape
        n_p, d_p = kernel_dims(kind, n, d)[:2]
        dom_p = rtac_support.assign_padded_rows(pad_dom(doms, n_p, d_p), var, val)
        ch_p = _padded_seed(var, n, n_p)
        if fused:
            res = fixpoint_rows_fn(kind, interpret)(net_g, dom_p, ch_p, idx)
        else:
            res = rtac.enforce_rows_generic(
                net_g, dom_p, ch_p, idx, revise_rows_fn=rows_fn(kind, interpret)
            )
        return res._replace(dom=res.dom[:, :n, :d])

    return assign_enforce_rows
