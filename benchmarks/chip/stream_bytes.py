"""The least HBM bytes of the streamed fixpoint kernel, and what the
per-layer metrics of a cell that runs it read.

``rtac_fixpoint_packed_stream`` (`repro.kernels.rtac_support`) runs a row's
recurrence in VMEM and reads its network from HBM sweep by sweep. A sweep
revises only the variables y its seed names (Prop. 2), and revising y needs
y's slice of the network: its packed words, W·N int32, and its spread mask,
N int8. So a row must move, at the least, that slice for each variable each
of its sweeps revised (``kernel.revised_vars`` sums them), plus once its
domain in and out (2·N int32), its seed (n int32) and its flags (2 int32).
In kernel coordinates n_p variables of d_p values, N = n_p·d_p lanes,
W = ⌈d_p / 32⌉ words:

    per revised variable   W·N·4 + N
    per row                2·N·4 + n_p·4 + 8

The count follows from the recurrence's semantics alone, so no correct
row-wise kernel moves less, whatever tiles it reads.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Optional, Tuple

from . import cells
from .kernel_bytes import words
from .readers import _kernel_s


def revised_var_bytes(n_p: int, d_p: int) -> int:
    """Bytes of one variable's network slice: its words and its mask."""
    lanes = n_p * d_p
    return words(d_p) * lanes * 4 + lanes


def row_bytes(n_p: int, d_p: int) -> int:
    """Bytes every row moves once: domain in and out, seed, flags."""
    return 2 * n_p * d_p * 4 + n_p * 4 + 2 * 4


def least_bytes(n_p: int, d_p: int, revised: int, rows: int) -> int:
    return revised * revised_var_bytes(n_p, d_p) + rows * row_bytes(n_p, d_p)


def config_dims(config: str, root: Path = cells.ROOT) -> Tuple[int, int]:
    """Kernel (n_p, d_p) of a configuration of `BENCHMARK.json`, by name."""
    from repro.kernels.ops import kernel_dims

    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == config)
    prob = json.loads((root / entry["file"]).read_text())["problem"]
    return tuple(kernel_dims("packed", prob["n"], prob["d"])[:2])


def roofline_pct(rec: dict, dims: Tuple[int, int]) -> Optional[float]:
    """The streamed kernel's least bytes over the peak bandwidth, as a share
    of its device time; None where no row streamed."""
    seconds, c = _kernel_s(rec), rec["counters"]
    revised, rows = c.get("kernel.revised_vars", 0), c.get("driver.rows", 0)
    if not seconds or not revised:
        return None
    least_s = least_bytes(*dims, revised, rows) / rec["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds


def mib_per_row(rec: dict) -> Optional[float]:
    """Network and mask MiB the streamed kernel read per row."""
    c = rec["counters"]
    streamed, rows = c.get("kernel.stream_bytes", 0), c.get("driver.rows", 0)
    return streamed / rows / 2**20 if streamed and rows else None
