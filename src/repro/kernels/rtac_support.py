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

Two launches share that sweep: `revise_rows` (one sweep, the stepped path's
per-iteration kernel) and `fixpoint_rows` (the whole recurrence in one launch:
a `while_loop` inside the kernel, domains resident in VMEM). The grid runs
over instance blocks of ``block_r`` rows; `max_block_r` sizes it from an
explicit VMEM budget.
"""

from __future__ import annotations

import functools

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


def max_block_r(encoding: str, n: int, d: int, budget: int = VMEM_BUDGET) -> int:
    """Largest power-of-two block_r (≤ 8) whose cell fits ``budget``; 0 if
    not even one row fits (the network must then stream from HBM)."""
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


# ---------------------------------------------------------------------------
# The sweep (shared by both launches)
# ---------------------------------------------------------------------------


def _layout(n: int, d: int):
    """(blk, a): blk[y, j] — lane j = x·d + a belongs to variable y; a[y, j]
    = j - y·d, the value index inside y's own lane block."""
    y = jax.lax.broadcasted_iota(jnp.int32, (n, n * d), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n * d), 1)
    a = j - y * d
    return (a >= 0) & (a < d), a


def _per_var(blk, v):
    """(b, 1, N) 0/1 -> (b, n, 1): does variable y hold any set lane."""
    return jnp.max(jnp.where(blk[None], v, 0), axis=2, keepdims=True)


def _sweep(encoding, cons, mask, v, seed, blk, a):
    """One Jacobi revise of ``b`` rows -> violated (b, 1, N) int32 0/1."""
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
    blk, a = _layout(mask_ref.shape[1], d)
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
