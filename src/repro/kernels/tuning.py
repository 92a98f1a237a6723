"""VMEM-aware tile selection — the TPU analogue of the paper's §4.3
occupancy balancing (block size vs shared-memory footprint vs resident
blocks).  This is the SINGLE row-tile picker: every scan kernel
(``gspn_scan.py``, ``gspn_multidir.py``) routes through
:func:`pick_row_tile`; ``gspn_scan.pick_row_tile`` survives only as a
thin wrapper over it for the old call signature.

The fused scan keeps per-grid-cell working set
``(x + wl + wc + wr + lam + out) tiles + f32 stages + carry`` resident in
VMEM.  The tuner picks the largest admissible row tile that (a) divides
the scan length, (b) satisfies Mosaic's block-shape rule, (c) keeps the
working set inside the VMEM budget, and (d) leaves headroom for
double-buffered pipelining (factor 2 on the streamed operands — Pallas
prefetches the next tile while the current one computes).
"""

from __future__ import annotations

import dataclasses

# The scoped-VMEM limit every scan kernel passes to Mosaic
# (``vmem_limit_bytes``), and the budget the tuner admits working sets
# against — one number, so an admitted tile is one the compiler accepts.
# A v5e TensorCore has 128 MiB of VMEM; half of it leaves the compiler
# room for its own buffers.
VMEM_BYTES = 64 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class TileChoice:
    row_tile: int
    working_set_bytes: int
    n_grid_steps: int


def sublane_rows(dtype_bytes: int) -> int:
    """Rows of one native VMEM tile for a streamed dtype: 8 for 4-byte,
    16 for packed 2-byte, 32 for 1-byte elements."""
    return 8 * 4 // dtype_bytes


def tile_admissible(row_tile: int, h: int, dtype_bytes: int) -> bool:
    """Mosaic's block-shape rule for the row axis: a row tile is a
    power-of-two multiple of the dtype's sublane tile, or the whole scan
    length.  (Every dividing multiple would satisfy Mosaic; powers of two
    keep the candidate set small.)"""
    if row_tile < 1 or h % row_tile:
        return False
    if row_tile == h:
        return True
    return (row_tile % sublane_rows(dtype_bytes) == 0
            and row_tile & (row_tile - 1) == 0)


def admissible_tiles(h: int, dtype_bytes: int, cap: int) -> list[int]:
    """Ascending admissible row tiles up to ``cap``; the whole scan
    length when no smaller tile is admissible."""
    tiles = [t for t in range(1, min(h, cap) + 1)
             if tile_admissible(t, h, dtype_bytes)]
    return tiles or [h]


def stage_rows(row_tile: int, dtype_bytes: int, pipeline_depth: int) -> int:
    """Rows of each stream's f32 staging buffer.  Depth 2 stages the
    whole tile (the transposed ``(T, G, W)`` copy its row loop indexes);
    depth 1 widens narrow streams one sublane group at a time (the whole
    tile when the tile is not a multiple of the group); f32 streams at
    depth 1 are read in place."""
    if pipeline_depth >= 2:
        return row_tile
    if dtype_bytes >= 4:
        return 0
    sub = sublane_rows(dtype_bytes)
    return sub if row_tile % sub == 0 else row_tile


def scan_working_set(row_tile: int, w: int, dtype_bytes: int,
                     n_streams: int = 6, double_buffer: bool = True,
                     carry_dtype_bytes: int = 4,
                     pipeline_depth: int = 1, planes: int = 1) -> int:
    """Bytes resident per grid cell: n_streams streamed tiles (+ their
    prefetch copies), their f32 staging buffers, and the carry row.

    ``dtype_bytes`` is the STREAMED dtype (bf16 halves every tile);
    ``carry_dtype_bytes`` is the VMEM carry row's dtype, kept separate so
    the accounting stays honest under the mixed-precision policy
    (DESIGN.md §10: bf16 streams, f32 carry).

    ``pipeline_depth=2`` is the plane-blocked staged pipeline (DESIGN.md
    §12): each grid step holds ``planes`` (= G) planes of every stream,
    and each stream keeps a whole-tile f32 staging copy, so every term
    scales with ``planes``.  At depth 1 a grid step holds one plane;
    narrow streams add a one-group f32 stage (:func:`stage_rows`).  Rows
    and lanes are counted as VMEM holds them: padded to the dtype's
    sublane tile and to 128 lanes, so a 7-wide vision grid costs what
    a 128-wide one does.
    """
    planes = planes if pipeline_depth >= 2 else 1
    mult = 2 if double_buffer else 1
    lanes = _round_up(w, 128)               # VMEM pads W to whole lanes
    rows = _round_up(row_tile, sublane_rows(dtype_bytes))
    ws = n_streams * rows * lanes * dtype_bytes * mult
    ws += n_streams * stage_rows(row_tile, dtype_bytes, pipeline_depth) \
        * lanes * 4
    ws += lanes * carry_dtype_bytes
    return ws * planes


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pick_row_tile(h: int, w: int, dtype_bytes: int = 4,
                  vmem_budget: int = VMEM_BYTES, cap: int = 512,
                  n_streams: int = 6,
                  carry_dtype_bytes: int = 4,
                  pipeline_depth: int = 1, planes: int = 1) -> TileChoice:
    """Largest admissible row tile (:func:`tile_admissible`) whose working
    set fits; the smallest admissible one when none fits."""
    tiles = admissible_tiles(h, dtype_bytes, cap)

    def ws(t):
        return scan_working_set(t, w, dtype_bytes, n_streams,
                                carry_dtype_bytes=carry_dtype_bytes,
                                pipeline_depth=pipeline_depth,
                                planes=planes)

    fitting = [t for t in tiles if ws(t) <= vmem_budget]
    best = fitting[-1] if fitting else tiles[0]
    return TileChoice(row_tile=best, working_set_bytes=ws(best),
                      n_grid_steps=h // best)


# ---------------------------------------------------------------------------
# Precision-policy routing (DESIGN.md §10/§11).
#
# Call sites must not guess byte widths: the streamed itemsize follows the
# policy's compute dtype and the carry itemsize its carry dtype.  This is
# the fix for the sites that passed dtype_bytes=4 regardless of the
# active policy (benchmarks, sp) — they now resolve a named preset here.
# ---------------------------------------------------------------------------

def policy_itemsizes(precision) -> tuple[int, int]:
    """(streamed_bytes, carry_bytes) for a ``configs.base`` precision
    preset name or Precision instance."""
    import jax.numpy as jnp

    from repro.configs.base import resolve_precision  # lazy: configs
    p = resolve_precision(precision)                  # import kernels
    return (jnp.dtype(p.compute_dtype).itemsize,
            jnp.dtype(p.carry_dtype).itemsize)


def pick_row_tile_for_policy(h: int, w: int, precision,
                             vmem_budget: int = VMEM_BYTES, cap: int = 512,
                             n_streams: int = 6,
                             pipeline_depth: int = 1) -> TileChoice:
    """``pick_row_tile`` with stream/carry bytes resolved from the
    mixed-precision policy instead of hand-passed constants.

    NOTE: the launch-site heuristic fallback caps at
    ``autotune.DEFAULT_CAP`` (256); pass ``cap=autotune.DEFAULT_CAP``
    (and the depth the launch would run at) when reporting what a
    launch's fallback would pick."""
    stream_b, carry_b = policy_itemsizes(precision)
    return pick_row_tile(h, w, stream_b, vmem_budget=vmem_budget, cap=cap,
                         n_streams=n_streams, carry_dtype_bytes=carry_b,
                         pipeline_depth=pipeline_depth)
