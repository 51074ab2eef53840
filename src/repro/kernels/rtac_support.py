"""RTAC revise and fixpoint kernels — one body, two constraint encodings.

TPU adaptation of the paper's Alg. 1 lines 14-16 (DESIGN.md §4). Every array
a kernel touches is 2-D per row with the flattened ``(x, a)`` value index on
the lanes, so no block or in-kernel value ever splits or re-tiles the lane
axis (Mosaic refuses such reshapes), and every BlockSpec is full in its last
two dimensions (legal under the (8, 128) rule at any shape):

  dom    (R, 1, N)    int32  row r's domain, N = n·d, lane j = x·d + a
  seed   (R, n, 1)    int32  the Prop. 2 revision seed, one sublane per y
  mask   (R, n, N)    int8   mask[r, y, x·d + a] = constrained(x, y)
  cons   dense:  (R, N, N)     int8   cons[(y, b), (x, a)] = C[x, y, a, b]
         packed: (R, W, n, N)  int32  bit b of word w = C[x, y, a, 32w + b]

A sweep (Jacobi: it reads only the pre-sweep domain) computes, for every
constrained neighbour y, whether (x, a) keeps a support in dom(y):

- dense: ``cnt = spread @ cons`` on the MXU in int8, where ``spread[y, (y',
  b)] = dom[y', b]·[y = y']`` is the domain laid block-diagonally over the
  sublanes (built from two iotas, never a reshape);
- packed: the domain word of each y is summed over its lanes (the bits are
  disjoint, so the sum is the OR), and ``has = any_w(cons_w & word_w) != 0``
  on the VPU — 32 values per word, 8x fewer constraint bytes than int8.

``violated[(x, a)] = any_y(seed[y] & mask[y, (x, a)] & ~has[y, (x, a)])`` is
a sublane reduction, so it lands on the lanes, in the layout of ``dom``.

Three launches share that sweep: `revise_rows` (one sweep, the stepped path's
per-iteration kernel), `fixpoint_rows` (the whole recurrence in one launch:
a `while_loop` inside the kernel, domains resident in VMEM) and, where not
one row's network fits VMEM, `fixpoint_rows_stream` (the same recurrence, the
packed network streamed from HBM in y-tiles, each swept by `_sweep`). The
grid runs over instance blocks of ``block_r`` rows; `max_block_r` sizes it
from an explicit VMEM budget, and `stream_ty` sizes the streamed tile.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

ENCODINGS = ("dense", "packed")

#: VMEM a kernel may plan for and is allowed to use (v5e has 128 MiB per
#: core; the compiler's default scoped limit is far below that)
VMEM_BUDGET = 96 * 1024 * 1024


def words_per_domain(d: int) -> int:
    return -(-d // 32)


def vmem_bytes(encoding: str, n: int, d: int, block_r: int) -> int:
    """Planned VMEM of one grid cell: double-buffered operands plus ~8 live
    (n, N) int32 temporaries of the sweep, per row."""
    nd = n * d
    if encoding == "dense":
        cons = nd * nd
    else:
        cons = words_per_domain(d) * n * nd * 4
    operands = cons + n * nd + 8 * nd + 4 * n + 8
    return block_r * (2 * operands + 8 * n * nd * 4)


def max_block_r(encoding: str, n: int, d: int, budget: Optional[int] = None) -> int:
    """Largest power-of-two block_r (≤ 8) whose cell fits ``budget`` (default
    `VMEM_BUDGET`); 0 if not even one row fits (the network must then stream
    from HBM: `fixpoint_rows_stream`)."""
    budget = VMEM_BUDGET if budget is None else budget
    for br in (8, 4, 2, 1):
        if vmem_bytes(encoding, n, d, br) <= budget:
            return br
    return 0


def _compiler_params(encoding: str, n: int, d: int, block_r: int):
    need = vmem_bytes(encoding, n, d, block_r)
    if need > VMEM_BUDGET:
        raise ValueError(
            f"{encoding} kernel at (n={n}, d={d}), block_r={block_r} plans "
            f"{need / 2**20:.1f} MiB of VMEM, over the {VMEM_BUDGET / 2**20:.0f} MiB budget"
        )
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET)


#: the largest y-extent of one streamed tile. A sweep skips every tile whose
#: variables carry no revision seed, and the first sweep after an assignment
#: seeds one variable, so a shorter tile reads less of the network there; a
#: longer one waits on fewer DMAs
STREAM_TY_MAX = 64


def stream_tile_bytes(n: int, d: int, ty: int) -> int:
    """HBM bytes one streamed tile moves: the packed words and the spread
    mask of ``ty`` variables y, over all N = n·d lanes."""
    return words_per_domain(d) * ty * n * d * 4 + ty * n * d


def stream_vmem_bytes(n: int, d: int, ty: int) -> int:
    """Planned VMEM of the streamed fixpoint (packed, one row per grid cell):
    double-buffered tiles, ~8 live (ty, N) int32 temporaries of the sweep,
    and the resident row: domain in and out (double-buffered) and the
    violated accumulator, (1, N) int32 each, plus the seed block
    (double-buffered) and the changed scratch, (n, 1) int32 padded to 128
    lanes."""
    nd = n * d
    return 2 * stream_tile_bytes(n, d, ty) + 8 * ty * nd * 4 + 5 * nd * 4 + 3 * n * 128 * 4


def stream_ty(n: int, d: int, budget: Optional[int] = None) -> int:
    """The y-extent of a streamed tile at kernel shape (n, d): the largest
    multiple of 8, up to min(n, `STREAM_TY_MAX`), whose plan fits ``budget``
    (default `VMEM_BUDGET`); 0 if none does. ``n`` is a multiple of 8, and a
    last tile that would run past it starts at n - ty instead."""
    budget = VMEM_BUDGET if budget is None else budget
    for ty in range(min(n, STREAM_TY_MAX) // 8 * 8, 0, -8):
        if stream_vmem_bytes(n, d, ty) <= budget:
            return ty
    return 0


def stream_tile_starts(n: int, ty: int):
    """First variable of each streamed tile: t·ty, the last one pulled back
    to n - ty so that every tile is whole (it then overlaps its neighbour;
    a variable revised twice in one sweep gives the same answer twice)."""
    return [min(t * ty, n - ty) for t in range(-(-n // ty))]


# ---------------------------------------------------------------------------
# The sweep (shared by both launches)
# ---------------------------------------------------------------------------


def _layout(rows: int, nd: int, d: int, y0=0):
    """(blk, a) over the y-extent ``[y0, y0 + rows)`` of N = ``nd`` lanes:
    blk[i, j] — lane j = x·d + a belongs to variable y = y0 + i; a[i, j] =
    j - y·d, the value index inside y's own lane block. The whole network is
    the extent ``[0, n)``; a streamed tile is a shorter one."""
    y = jax.lax.broadcasted_iota(jnp.int32, (rows, nd), 0) + y0
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, nd), 1)
    a = j - y * d
    return (a >= 0) & (a < d), a


def _per_var(blk, v):
    """(b, 1, N) 0/1 -> (b, n, 1): does variable y hold any set lane."""
    return jnp.max(jnp.where(blk[None], v, 0), axis=2, keepdims=True)


def _sweep(encoding, cons, mask, v, seed, blk, a):
    """One Jacobi revise of ``b`` rows -> violated (b, 1, N) int32 0/1, over
    the y-extent that ``cons``, ``mask``, ``seed`` and the layout (``blk``,
    ``a``) cover: the whole network, or one streamed tile of it."""
    if encoding == "dense":
        spread = jnp.where(blk[None], v, 0).astype(jnp.int8)  # (b, n, N)
        cnt = jnp.einsum(
            "bnk,bkm->bnm", spread, cons, preferred_element_type=jnp.int32
        )
        has = cnt > 0
    else:
        has = None
        for w in range(cons.shape[1]):
            in_word = blk & (a >= 32 * w) & (a < 32 * w + 32)
            weight = jnp.where(
                in_word, jnp.left_shift(jnp.int32(1), jnp.clip(a - 32 * w, 0, 31)), 0
            )
            word = jnp.sum(v * weight[None], axis=2, keepdims=True, dtype=jnp.int32)
            hit = (cons[:, w] & word) != 0  # (b, n, N)
            has = hit if has is None else has | hit
    dead = (~has) & (mask != 0) & (seed != 0)
    return jnp.max(dead.astype(jnp.int32), axis=1, keepdims=True)


def _load(cons_ref, mask_ref, d):
    blk, a = _layout(mask_ref.shape[1], mask_ref.shape[2], d)
    return cons_ref[...], mask_ref[...].astype(jnp.int32), blk, a


def _revise_kernel(cons_ref, dom_ref, seed_ref, mask_ref, viol_ref, *, encoding, d):
    cons, mask, blk, a = _load(cons_ref, mask_ref, d)
    viol_ref[...] = _sweep(encoding, cons, mask, dom_ref[...], seed_ref[...], blk, a)


def _fixpoint_kernel(
    cons_ref, dom_ref, seed_ref, mask_ref, dom_out_ref, ok_out_ref, k_out_ref,
    *, encoding, d,
):
    """``block_r`` rows to their AC fixpoint in one launch. Per-row semantics
    equal `rtac.enforce_rows_generic`: a row is active while consistent with a
    non-empty seed, an inactive row's seed is zeroed (its domain freezes), and
    ``k`` counts only the sweeps the row was active."""
    cons, mask, blk, a = _load(cons_ref, mask_ref, d)
    v0 = dom_ref[...]
    ok0 = jnp.min(_per_var(blk, v0), axis=1, keepdims=True)  # (b, 1, 1)

    def cond(s):
        _, ch, ok, _ = s
        return jnp.max(ok * jnp.max(ch, axis=1, keepdims=True)) > 0

    def body(s):
        v, ch, ok, k = s
        active = ok * jnp.max(ch, axis=1, keepdims=True)  # (b, 1, 1)
        viol = _sweep(encoding, cons, mask, v, ch * active, blk, a)
        new_v = v * (1 - viol)
        changed = _per_var(blk, v - new_v)
        ok2 = ok * jnp.min(_per_var(blk, new_v), axis=1, keepdims=True)
        return new_v, changed, ok2, k + active

    v, _, ok, k = jax.lax.while_loop(
        cond, body, (v0, seed_ref[...] * ok0, ok0, jnp.zeros_like(ok0))
    )
    dom_out_ref[...] = v
    ok_out_ref[...] = ok
    k_out_ref[...] = k


def _fixpoint_stream_kernel(
    idx_ref, dom_ref, seed_ref, ok_ref, cons_hbm, mask_hbm,
    dom_out_ref, ok_out_ref, k_out_ref, revised_out_ref, tiles_out_ref,
    cons_buf, mask_buf, sem, ch_ref, viol_ref, tiles_ref, *, d, ty,
):
    """One row to its AC fixpoint against network ``idx[row]`` of the
    stacked tables, which stay in HBM. Each sweep streams the network in
    y-tiles (double-buffered DMAs) and applies `_sweep` to each; the domain,
    the changed set and the violated accumulator stay in VMEM. A tile whose
    variables carry no revision seed is neither read nor revised (Prop. 2).
    ``ok`` is the row's domain non-empty at entry, and ``seed`` is already
    zero where it is not. Semantics equal `_fixpoint_kernel` with one row a
    cell; the row also reports the seeded variables summed over its sweeps
    (``revised``) and the tiles it read."""
    n, nd = ch_ref.shape[0], dom_ref.shape[2]
    n_tiles = mask_hbm.shape[1]
    inst = idx_ref[pl.program_id(0)]

    def y0_of(t):
        if isinstance(t, int):
            return min(t * ty, n - ty)
        return pl.multiple_of(jnp.minimum(t * ty, n - ty), 8)

    def copies(t, slot):
        return (
            pltpu.make_async_copy(
                cons_hbm.at[inst, :, pl.ds(y0_of(t), ty)], cons_buf.at[slot], sem.at[0, slot]
            ),
            pltpu.make_async_copy(mask_hbm.at[inst, t], mask_buf.at[slot], sem.at[1, slot]),
        )

    def seeded(t):
        return jnp.max(ch_ref[pl.ds(y0_of(t), ty), :]) > 0

    def start(t, slot):
        for c in copies(t, slot):
            c.start()

    def sweep(v):
        """violated (1, 1, N) of one Jacobi sweep seeded by ``ch_ref``."""
        viol_ref[...] = jnp.zeros_like(viol_ref)

        @pl.when(seeded(0))
        def _():
            start(0, 0)

        def tile(t, carry):
            slot = t % 2

            @pl.when(jnp.logical_and(t + 1 < n_tiles, seeded(t + 1)))
            def _():
                start(t + 1, 1 - slot)

            @pl.when(seeded(t))
            def _():
                for c in copies(t, slot):
                    c.wait()
                y0 = y0_of(t)
                blk, a = _layout(ty, nd, d, y0)
                viol_ref[...] = jnp.maximum(viol_ref[...], _sweep(
                    "packed", cons_buf[slot][None], mask_buf[slot].astype(jnp.int32)[None],
                    v, ch_ref[pl.ds(y0, ty), :][None], blk, a,
                ))
                tiles_ref[0] += 1

            return carry

        jax.lax.fori_loop(0, n_tiles, tile, 0)
        return viol_ref[...]

    one = jnp.ones((1, 1, 1), jnp.int32)

    def settle():
        """Tile by tile: whether every domain (``dom_out_ref``) is non-empty,
        and which variables lost a value (``viol_ref``: the values the sweep
        removed), written to ``ch_ref``, with whether any did. A tile's
        variables own the lanes [y0·d, (y0 + ty)·d): where that window is
        (8, 128)-aligned only it is read."""
        ok, any_changed = one, 0 * one
        for y0 in stream_tile_starts(n, ty):
            lo, width = (y0 * d, ty * d) if (y0 * d) % 128 == (ty * d) % 128 == 0 else (0, nd)
            blk, _ = _layout(ty, width, d, y0 - lo // d)
            changed = _per_var(blk, viol_ref[:, :, lo:lo + width])  # (1, ty, 1)
            ch_ref[y0:y0 + ty, :] = changed[0]
            ok = jnp.minimum(ok, jnp.min(_per_var(blk, dom_out_ref[:, :, lo:lo + width]),
                                         axis=1, keepdims=True))
            any_changed = jnp.maximum(any_changed, jnp.max(changed, axis=1, keepdims=True))
        return ok, any_changed

    def unchanged():
        """A sweep that removed nothing: no variable changed, and the
        domains, non-empty before it, still are."""
        ch_ref[...] = jnp.zeros_like(ch_ref)
        return one, 0 * one

    def cond(s):
        return jnp.max(s[4]) > 0

    def body(s):
        v, ok, k, revised, _ = s
        revised = revised + jnp.sum(ch_ref[...], axis=0, keepdims=True)[None]
        removed = v * sweep(v)
        new_v = v - removed
        viol_ref[...] = removed
        dom_out_ref[...] = new_v
        ok_new, any_changed = jax.lax.cond(jnp.max(removed) > 0, settle, unchanged)
        ok = ok * ok_new
        return new_v, ok, k + 1, revised, ok * any_changed

    tiles_ref[0] = 0
    ok0 = ok_ref[...]
    ch_ref[...] = seed_ref[0]
    go0 = ok0 * jnp.max(ch_ref[...], axis=0, keepdims=True)[None]
    zero = 0 * one
    v, ok, k, revised, _ = jax.lax.while_loop(cond, body, (dom_ref[...], ok0, zero, zero, go0))
    dom_out_ref[...] = v
    ok_out_ref[...] = ok
    k_out_ref[...] = k
    revised_out_ref[...] = revised
    tiles_out_ref[...] = jnp.full((1, 1, 1), tiles_ref[0], jnp.int32)


# ---------------------------------------------------------------------------
# Launches
# ---------------------------------------------------------------------------


def _in_specs(encoding, cons_shape, n, nd, block_r):
    if encoding == "dense":
        cons_spec = pl.BlockSpec((block_r, nd, nd), lambda g: (g, 0, 0))
    else:
        cons_spec = pl.BlockSpec((block_r, cons_shape[1], n, nd), lambda g: (g, 0, 0, 0))
    return [
        cons_spec,
        pl.BlockSpec((block_r, 1, nd), lambda g: (g, 0, 0)),
        pl.BlockSpec((block_r, n, 1), lambda g: (g, 0, 0)),
        pl.BlockSpec((block_r, n, nd), lambda g: (g, 0, 0)),
    ]


def _check(encoding, dom, seed, mask, d, block_r):
    if encoding not in ENCODINGS:
        raise ValueError(f"unknown encoding {encoding!r}; expected one of {ENCODINGS}")
    r, _, nd = dom.shape
    n = seed.shape[1]
    assert nd == n * d and mask.shape == (r, n, nd), (dom.shape, seed.shape, mask.shape)
    assert r % block_r == 0, (r, block_r)
    return r, n, nd


@functools.partial(jax.jit, static_argnames=("encoding", "d", "block_r", "interpret"))
def revise_rows(
    cons: Array, dom: Array, seed: Array, mask: Array,
    *, encoding: str, d: int, block_r: int, interpret: bool,
) -> Array:
    """R independent revise sweeps, row r against its own network. Returns
    violated (R, 1, N) int32 0/1."""
    r, n, nd = _check(encoding, dom, seed, mask, d, block_r)
    return pl.pallas_call(
        functools.partial(_revise_kernel, encoding=encoding, d=d),
        grid=(r // block_r,),
        in_specs=_in_specs(encoding, cons.shape, n, nd, block_r),
        out_specs=pl.BlockSpec((block_r, 1, nd), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1, nd), jnp.int32),
        compiler_params=_compiler_params(encoding, n, d, block_r),
        interpret=interpret,
        name=f"rtac_revise_{encoding}",
    )(cons, dom, seed, mask)


@functools.partial(jax.jit, static_argnames=("encoding", "d", "block_r", "interpret"))
def fixpoint_rows(
    cons: Array, dom: Array, seed: Array, mask: Array,
    *, encoding: str, d: int, block_r: int, interpret: bool,
):
    """R fixpoints in ONE launch. Returns (dom (R, 1, N) int32, consistent
    (R, 1, 1) int32, k (R, 1, 1) int32) — per row bit-identical to the
    stepped `rtac.enforce_rows_generic` path."""
    r, n, nd = _check(encoding, dom, seed, mask, d, block_r)
    row_spec = pl.BlockSpec((block_r, 1, 1), lambda g: (g, 0, 0))
    return pl.pallas_call(
        functools.partial(_fixpoint_kernel, encoding=encoding, d=d),
        grid=(r // block_r,),
        in_specs=_in_specs(encoding, cons.shape, n, nd, block_r),
        out_specs=[
            pl.BlockSpec((block_r, 1, nd), lambda g: (g, 0, 0)),
            row_spec,
            row_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, 1, nd), jnp.int32),
            jax.ShapeDtypeStruct((r, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((r, 1, 1), jnp.int32),
        ],
        compiler_params=_compiler_params(encoding, n, d, block_r),
        interpret=interpret,
        name=f"rtac_fixpoint_{encoding}",
    )(cons, dom, seed, mask)


@functools.partial(jax.jit, static_argnames=("d", "ty", "interpret"))
def fixpoint_rows_stream(
    cons: Array, mask_tiles: Array, idx: Array, dom: Array, seed: Array, ok: Array,
    *, d: int, ty: int, interpret: bool,
):
    """R packed fixpoints in ONE launch, row r against network ``idx[r]`` of
    the stacked tables, streamed from HBM a tile of ``ty`` variables at a
    time (`_fixpoint_stream_kernel`) — for networks no row of which fits
    VMEM (`max_block_r` 0). ``cons`` is the slot table (B, W, n, N) int32;
    ``mask_tiles`` (B, T, ty, N) int8 holds each tile's spread mask whole
    (`stream_tile_starts`), so every DMA is tile-aligned. ``ok`` (R, 1, 1)
    int32 says each row's domain is non-empty, and ``seed`` must be zero
    where it is not (`ops._fixpoint_stream`). Returns (dom (R, 1, N) int32,
    consistent, k, revised, tiles read), the last four (R, 1, 1) int32; dom,
    consistent and k are bit-identical to `fixpoint_rows`."""
    _, w, n, nd = cons.shape
    r = dom.shape[0]
    assert nd == n * d and seed.shape == (r, n, 1), (cons.shape, dom.shape, seed.shape)
    assert mask_tiles.shape[1:] == (len(stream_tile_starts(n, ty)), ty, nd), mask_tiles.shape
    row = lambda g, idx: (g, 0, 0)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    flag = pl.BlockSpec((1, 1, 1), row)
    return pl.pallas_call(
        functools.partial(_fixpoint_stream_kernel, d=d, ty=ty),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r,),
            in_specs=[pl.BlockSpec((1, 1, nd), row), pl.BlockSpec((1, n, 1), row), flag,
                      hbm, hbm],
            out_specs=[pl.BlockSpec((1, 1, nd), row), flag, flag, flag, flag],
            scratch_shapes=[
                pltpu.VMEM((2, w, ty, nd), jnp.int32),
                pltpu.VMEM((2, ty, nd), jnp.int8),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((n, 1), jnp.int32),
                pltpu.VMEM((1, 1, nd), jnp.int32),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((r, 1, nd), jnp.int32)]
        + [jax.ShapeDtypeStruct((r, 1, 1), jnp.int32)] * 4,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
        name="rtac_fixpoint_packed_stream",
    )(idx, dom, seed, ok, cons, mask_tiles)


def assign_padded_rows(dom_p: Array, var: Array, val: Array) -> Array:
    """Batched Alg. 2 ``assign`` in kernel (padded) coordinates — the fused
    front half of a frontier dispatch (DESIGN.md §8): row i's ``dom(var[i])``
    collapses to ``{val[i]}`` before the fixpoint runs, all in one traced
    program, so a search round never materializes assigned domains on the
    host. ``var[i] < 0`` marks a root row, left untouched. ``var``/``val``
    index *caller* coordinates (< n, < d), so the padded tail — absent
    values, unconstrained singleton variables — is preserved by construction.
    """
    r, _, d_p = dom_p.shape
    safe_var = jnp.maximum(var, 0)
    onehot = (jnp.arange(d_p, dtype=var.dtype)[None, :] == val[:, None]).astype(dom_p.dtype)
    assigned = dom_p.at[jnp.arange(r), safe_var].set(onehot)
    return jnp.where((var < 0)[:, None, None], dom_p, assigned)
