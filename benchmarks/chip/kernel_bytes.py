"""The least HBM bytes of the fused fixpoint kernel, from its shapes.

One row of ``rtac_fixpoint_<encoding>`` (`repro.kernels.rtac_support`) reads
its own constraint network, its spread constraint mask, its domain and its
revision seed, and writes its closed domain, its consistency flag and its
recurrence count; the recurrence between runs in VMEM, so each operand
crosses HBM once. In kernel coordinates n_p variables of d_p values,
N = n_p·d_p lanes, W = ⌈d_p / 32⌉ words:

    network  dense: N·N int8      packed: W·n_p·N int32
    mask     n_p·N int8
    domain   N int32 in, N int32 out
    seed     n_p int32
    flags    2 int32 (consistent, recurrences)
"""

from __future__ import annotations


def words(d_p: int) -> int:
    return -(-d_p // 32)


def fixpoint_row_bytes(encoding: str, n_p: int, d_p: int) -> int:
    """HBM bytes one row of the fused fixpoint kernel must move."""
    lanes = n_p * d_p
    if encoding == "dense":
        network = lanes * lanes
    elif encoding == "packed":
        network = words(d_p) * n_p * lanes * 4
    else:
        raise ValueError(f"unknown encoding {encoding!r}")
    return network + n_p * lanes + 2 * lanes * 4 + n_p * 4 + 2 * 4
