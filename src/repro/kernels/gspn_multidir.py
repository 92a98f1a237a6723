"""Fused multi-direction GSPN scan — the TPU analogue of the paper's §4.3
stream-based concurrency (DESIGN.md §2).

GSPN-1 ran the four directional passes as separate kernel streams; here
opposite directions are fused into ONE ``pallas_call`` whose leading grid
axis selects the direction:

* :func:`gspn_scan_bidir_pallas` — forward scan for one opposite pair
  (canonical top→bottom plus its bottom→top mirror).  The input ``x`` tile
  is shared between both directions via the BlockSpec index map — each x
  tile streams from HBM once per direction pair instead of once per
  direction in the flipped copy the naive path materialises, and the
  sequential grid gives the scheduler twice the pipelineable work per
  launch.
* :func:`gspn_scan_bidir_bwd_pallas` — the fused adjoint of the pair:
  direction 0's adjoint walks rows last→first, direction 1's first→last,
  again in one launch with no flipped copies.
* :func:`gspn_scan_quad_pallas` — all FOUR directions in a single launch
  for square grids: ``x`` and its transpose are stacked once at the
  dispatch boundary and the index map picks the orientation per direction
  (``d // 2``).  Forward-only; used by the benchmark ladder to demonstrate
  the paper's single-launch design point.

A full four-direction dispatch (the L→R/R→L pair handled by one transpose
at the dispatch boundary) therefore costs **two** launches for arbitrary
H×W — see ``repro.core.gspn.directional_scan`` — or one for square grids.

Direction handling is pure index arithmetic: for the reverse member of a
pair the H tiles are visited in reverse (index_map) and rows within a tile
iterate backwards (in the kernel's row walk).  No flipped copies of any operand
exist in either the forward or the adjoint pass.  Every launch here is
the shared kernel of ``gspn_scan.launch_scan`` with a direction grid axis.

Layout: x (G, H, W); taps/lam stacked per direction (2, G_w, H, W) /
(2, G, H, W).  Output (2, G, H, W): out[0] = top→bottom scan, out[1] =
bottom→top scan (both in the UNFLIPPED layout of x).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.gspn_scan import launch_scan
from repro.kernels.spec import ScanSpec


def _pair_spec(spec: ScanSpec | None, direction: str, dtype, *,
               channels_per_weight: int = 1, carry_dtype=jnp.float32,
               interpret: bool | None = None, row_tile: int | None = None,
               pipeline_depth: int | None = None) -> ScanSpec:
    """Build (from legacy kwargs) or normalise the spec of one fused
    pair/quad launch: these entry points own the ``multidir`` impl leg,
    the direction, and the streamed dtype (always the operands')."""
    if spec is None:
        spec = ScanSpec(channels_per_weight=channels_per_weight,
                        carry_dtype=str(jnp.dtype(carry_dtype)),
                        row_tile=row_tile, pipeline_depth=pipeline_depth,
                        interpret=interpret)
    changes = dict(direction=direction, impl="multidir",
                   stream_dtype=str(jnp.dtype(dtype)))
    if direction == "pair_bwd":
        changes["carry_dtype"] = "float32"   # adjoint carry is always f32
    return spec.with_(**changes)


def _stacked(a):
    """(D, P, H, W) -> (D·P, H, W): the kernels take 3-D blocks only (a
    4-D block's lane slice must be 128-aligned, which W < 128 is not)."""
    return a.reshape((-1,) + a.shape[2:])


# ---------------------------------------------------------------------------
# Fused opposite-direction pair: direction 0 walks top→bottom, direction 1
# bottom→top; x is SHARED — both directions read the same tiles in
# opposite order.
# ---------------------------------------------------------------------------

def gspn_scan_bidir_pallas(x, taps, lam2, *, spec: ScanSpec | None = None,
                           channels_per_weight: int = 1,
                           row_tile: int | None = None,
                           interpret: bool | None = None,
                           carry_dtype=jnp.float32,
                           pipeline_depth: int | None = None):
    """x: (G, H, W); taps: dict with wl/wc/wr each (2, G_w, H, W);
    lam2: (2, G, H, W).  Returns (2, G, H, W) — both directional scans.
    Configuration travels as ONE ``ScanSpec`` (DESIGN.md §14; the loose
    kwargs are the legacy construction path): streams in the operands'
    dtype, carries in ``spec.carry_dtype``; ``pipeline_depth=2`` is the
    staged pipeline (DESIGN.md §12)."""
    g, h, w = x.shape
    spec = _pair_spec(spec, "pair_fwd", x.dtype,
                      channels_per_weight=channels_per_weight,
                      carry_dtype=carry_dtype, interpret=interpret,
                      row_tile=row_tile, pipeline_depth=pipeline_depth)
    gw = g // spec.channels_per_weight
    plan = autotune.plan_for_spec(spec, h, w, c=g)
    out = launch_scan(
        spec, plan,
        [(x, g), (_stacked(taps["wl"]), gw), (_stacked(taps["wc"]), gw),
         (_stacked(taps["wr"]), gw), (_stacked(lam2), g)],
        adjoint=False, n_dirs=2, reversed_dirs=(1,), out_dtype=x.dtype,
        name="gspn_pair_fwd")
    return out.reshape(2, g, h, w)


def gspn_scan_bidir_bwd_pallas(dy2, wl2, wc2, wr2, *,
                               spec: ScanSpec | None = None,
                               channels_per_weight: int = 1,
                               row_tile: int | None = None,
                               interpret: bool | None = None,
                               pipeline_depth: int | None = None):
    """Fused adjoint of the pair scan.  dy2: (2, G, H, W); w*2:
    (2, G_w, H, W), all in the UNFLIPPED layout.  The adjoint of the
    top→bottom scan walks rows last→first, that of the bottom→top scan
    first→last — the forward pair's traversal with the roles swapped.
    Returns g2 = dL/dh (pre-output-layer) as (2, G, H, W) f32 — one
    launch, no flipped copies."""
    _, g_dim, h, w = dy2.shape
    # Streamed dtype is dy2's; the adjoint carry is three f32 tap·adjoint
    # rows regardless of policy (encoded by the "pair_bwd" direction leg —
    # _pair_spec forces it).
    spec = _pair_spec(spec, "pair_bwd", dy2.dtype,
                      channels_per_weight=channels_per_weight,
                      interpret=interpret, row_tile=row_tile,
                      pipeline_depth=pipeline_depth)
    gw = g_dim // spec.channels_per_weight
    plan = autotune.plan_for_spec(spec, h, w, c=g_dim)
    out = launch_scan(
        spec, plan,
        [(_stacked(dy2), g_dim), (_stacked(wl2), gw), (_stacked(wc2), gw),
         (_stacked(wr2), gw)],
        adjoint=True, n_dirs=2, reversed_dirs=(0,), out_dtype=jnp.float32,
        name="gspn_pair_bwd")
    return out.reshape(2, g_dim, h, w)


# ---------------------------------------------------------------------------
# Single-launch quad kernel (square grids).
# ---------------------------------------------------------------------------

def gspn_scan_quad_pallas(x, taps4, lam4, *, spec: ScanSpec | None = None,
                          channels_per_weight: int = 1,
                          row_tile: int | None = None,
                          interpret: bool | None = None,
                          carry_dtype=jnp.float32,
                          pipeline_depth: int | None = None):
    """All four directions in ONE ``pallas_call`` (square H == W only).

    x: (G, N, N).  taps4: dict wl/wc/wr each (4, G_w, N, N); lam4:
    (4, G, N, N) — directions ordered (tb, bt, lr, rl) with the lr/rl
    entries already in TRANSPOSED geometry (rows of entry 2/3 are the
    original columns).  ``x`` and its transpose are stacked once here; the
    index map then selects the orientation per direction (``d // 2``), so
    each grid step streams exactly one x tile — the paper's single-launch
    design point with no flipped copies.

    Returns (4, G, N, N): entries 0/1 in original orientation, entries 2/3
    transposed (callers undo the transpose at the dispatch boundary).
    Forward-only — training uses the pair dispatch (ops.gspn_scan_pair).
    """
    g, h, w = x.shape
    assert h == w, "quad single-launch dispatch requires a square grid"
    spec = _pair_spec(spec, "quad", x.dtype,
                      channels_per_weight=channels_per_weight,
                      carry_dtype=carry_dtype, interpret=interpret,
                      row_tile=row_tile, pipeline_depth=pipeline_depth)
    gw = g // spec.channels_per_weight
    plan = autotune.plan_for_spec(spec, h, w, c=g)
    xx = jnp.concatenate([x, jnp.swapaxes(x, -1, -2)])   # (2G, N, N)
    out = launch_scan(
        spec, plan,
        [(xx, g), (_stacked(taps4["wl"]), gw), (_stacked(taps4["wc"]), gw),
         (_stacked(taps4["wr"]), gw), (_stacked(lam4), g)],
        adjoint=False, n_dirs=4, reversed_dirs=(1, 3), out_dtype=x.dtype,
        name="gspn_quad_fwd")
    return out.reshape(4, g, h, w)
