"""Spatial sequence parallelism for the GSPN line scan (DESIGN.md §8).

PR 1 fused the multi-direction dispatch, but every scan still ran on ONE
device — the mesh axes only sharded weights, so resolution / folded
sequence length were capped by a single chip's VMEM/HBM.  This module
shards the scan dimension itself across a ``seq`` mesh axis, following the
LASP/LASP-2 observation (arXiv 2404.02882, 2502.07563) that linear
recurrences admit sequence parallelism with a SINGLE compact boundary
exchange per scan instead of any full-activation collective.

Decomposition.  The canonical recurrence (top→bottom over rows, W lanes)

    h[i] = M[i] h[i-1] + lam[i]·x[i],   M[i] tridiagonal from (wl, wc, wr)

is linear in the carry, so partitioning rows into K contiguous blocks
(one per ``seq`` shard) gives, for block k with incoming boundary
``b_k = h[first_row_k - 1]``:

    h[i] = h_loc[i] + (∏_{r=first_k..i} M[r]) · b_k

where ``h_loc`` is the block-local scan with zero incoming state.  Each
device therefore computes, fully in parallel:

  1. ``h_loc``  — the existing fused kernel on its local rows;
  2. ``T_k = ∏_{r in block k} M[r]`` — the (W, W) *boundary transfer
     operator*, one per weight group (compact mode amortises it over
     ``channels_per_weight`` channels);
  3. its outgoing uncorrected boundary ``bl_k`` (last local row of
     ``h_loc``).

Boundary composition is associative —
``(T_b, b_b) ∘ (T_a, b_a) = (T_b T_a, T_b b_a + b_b)`` — so the corrected
incoming boundaries ``b_k`` compose across blocks with ONE logical
exchange.  Two strategies (``strategy=``):

* ``"ppermute"``  — a K-1 step neighbour chain; each hop forwards one
  boundary column (G·W floats) and folds it through the local ``T_k``
  matvec.  Lowest traffic, latency linear in K: right for small meshes.
* ``"allgather"`` — one log-depth all-gather of the compact ``(T_k,
  bl_k)`` pairs; every device then folds its own prefix locally with K
  cheap matvecs.  One collective round: right for larger meshes.
* ``"pair_allgather"`` — the fused opposite-direction pair shares ONE
  all-gather of both directions' stacked ``(T, b)`` states (LASP-2,
  arXiv 2502.07563).  Only meaningful for pair calls; see below.
* ``"auto"``      — per-direction calls: ppermute for K ≤ 4, allgather
  beyond; pair calls: pair_allgather.

A final correction pass propagates ``b_k`` homogeneously through the
block (3 FMAs/element — same shape as the local scan, no extra HBM
round-trip) and adds it to ``h_loc``.

Fused pair, single collective, compute/comm overlap.  The model path
dispatches opposite directions as ONE fused pair
(``ops.gspn_scan_pair`` / ``core.gspn._multi_directional_scan``), and
:func:`gspn_scan_sp_pair` runs that pair with a single boundary
collective instead of two independent exchanges.  The key enabler is
:func:`block_boundary_states`: one cheap affine operator scan carries
``(T, b)`` jointly, producing each direction's complete exchange payload
WITHOUT the full-width local scan.  Both payloads (plus the adjoint's
edge weight rows, which previously cost a separate single-row ppermute)
are stacked into one array and all-gathered; the expensive block-local
pair scan is issued AFTER the collective but consumes nothing from it,
so XLA's latency-hiding scheduler can overlap the exchange with the
local compute.  The ``custom_vjp`` backward is itself an opposite pair
(the fwd member's adjoint runs in reverse and vice versa) and reuses the
same machinery — one more fused collective, zero ppermutes.
``SPConfig.exchange_mode`` exposes the schedule for measurement:
``"overlap"`` (production), ``"serial"`` (an optimization_barrier pins
the gather before the local scan — the exposed-exchange baseline), and
``"skip"`` (no collective — the timing floor); ``benchmarks/sp_scaling``
reports overlap efficiency = hidden / exposed exchange time from the
three.

Backward.  ``gspn_scan_sp`` is a ``custom_vjp``: the adjoint of the scan
is the SAME block-parallel engine run in reverse — adjoint taps are the
next row's weights with left/right roles transposed
(``wl~ = shift_right(wr[i+1])``, ``wc~ = wc[i+1]``,
``wr~ = shift_left(wl[i+1])``), the boundary exchange direction flips
(last block is first in scan order), and one extra single-row ppermute
fetches the neighbour block's first weight row.  Parameter/input
gradients are then purely local, using the forward incoming boundary
(saved as a residual) as the cross-block previous row.

Non-divisible scan lengths are handled by zero-padding rows at the scan
*end* (zero taps/lam ⇒ padded rows carry exact zeros through both the
forward and adjoint recurrences) and slicing the pad off outside the
shard_map, so block shapes stay static and equal.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat, obs
from repro.kernels import gspn_scan as _pk
from repro.kernels import ref as _ref
from repro.kernels.spec import ScanSpec

STRATEGIES = ("auto", "ppermute", "allgather", "pair_allgather")

# How the fused-pair exchange is scheduled against the local scan.
EXCHANGE_MODES = ("overlap", "serial", "skip")

# auto strategy: neighbour chain while the latency term (K-1 hops) stays
# small, one-shot all-gather of (T, b) pairs beyond.
PPERMUTE_MAX_BLOCKS = 4


@dataclasses.dataclass(frozen=True)
class SPConfig:
    """Static (hashable) configuration of one sharded scan call.

    Everything the block-LOCAL launch needs (inner impl, channel mode,
    dtype policy, tile/pipeline, ``boundary="sp_block_local"``) lives in
    the embedded :class:`ScanSpec` — the same object handed to the fused
    kernel and through it to the autotuner, so the sp path shares the one
    spec-keyed tuning cache (DESIGN.md §11/§14).  SPConfig itself only
    adds the cross-device legs: mesh axis, block count, exchange strategy
    and wire dtype.
    """
    axis_name: str = "seq"
    n_blocks: int = 1
    strategy: str = "auto"
    # Wire dtype of the boundary exchange (DESIGN.md §10): the (T, b)
    # payloads are cast to this before every collective hop; the
    # associative composition itself always runs in f32.  bf16 halves the
    # exchanged bytes — the one cross-device traffic of the scan.  Stays
    # OUTSIDE the spec: it shapes the exchange, not the kernel launch.
    boundary_dtype: str = "float32"
    # Fused-pair exchange schedule (EXCHANGE_MODES).  "overlap" is
    # production: the collective is issued before the local scan and
    # nothing forces it to finish first.  "serial"/"skip" exist for the
    # sp_scaling overlap rung (exposed-exchange baseline / no-exchange
    # floor); "skip" produces WRONG cross-block values by construction.
    exchange_mode: str = "overlap"
    # Block-local launch spec (impl resolved to a concrete kernel,
    # boundary="sp_block_local").
    spec: ScanSpec = ScanSpec(impl="xla", boundary="sp_block_local")

    def resolved_strategy(self, *, pair: bool = False) -> str:
        """The concrete exchange strategy for this config.

        ``pair=True`` resolves for the fused opposite-direction pair:
        ``auto`` picks the single-collective ``pair_allgather`` there,
        while an explicit per-direction strategy (``ppermute`` /
        ``allgather``) is honoured as the fallback knob.  Per-direction
        calls degrade ``pair_allgather`` to ``allgather`` (the pair
        strategy has no single-direction form).
        """
        if self.strategy != "auto":
            if not pair and self.strategy == "pair_allgather":
                return "allgather"
            return self.strategy
        if pair:
            return "pair_allgather"
        return ("ppermute" if self.n_blocks <= PPERMUTE_MAX_BLOCKS
                else "allgather")

    # Compat views over the embedded spec.
    @property
    def inner_impl(self) -> str:
        return self.spec.impl

    @property
    def channels_per_weight(self) -> int:
        return self.spec.channels_per_weight

    @property
    def carry_dtype(self) -> str:
        return self.spec.carry_dtype


def _resolve_inner(inner_impl: str) -> str:
    if inner_impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if inner_impl not in ("pallas", "xla"):
        raise ValueError(f"unknown inner impl {inner_impl!r}")
    return inner_impl


def _resolve_inner_pair(inner_impl: str) -> str:
    """Block-local impl for the fused pair: the bidirectional kernel on
    TPU, the XLA oracle elsewhere ("pallas" is accepted as an alias)."""
    if inner_impl in ("auto", "pallas"):
        return "multidir" if jax.default_backend() == "tpu" else "xla"
    if inner_impl not in ("multidir", "xla"):
        raise ValueError(f"unknown pair inner impl {inner_impl!r}")
    return inner_impl


def collectives_in_jaxpr(fn, *args):
    """[(primitive_name, invar_shape, invar_dtype)] for every collective
    in ``fn``'s jaxpr, recursing into sub-jaxprs (shard_map bodies,
    scans, custom_vjp calls).

    The one shared definition of "collectives per exchange": the sp tests
    pin counts with it and ``benchmarks/sp_scaling`` reports them from
    it, so the instrument cannot drift from the contract being tested.
    """
    kinds = ("all_gather", "psum", "ppermute", "all_to_all", "pgather",
             "reduce_scatter")
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            nm = eqn.primitive.name
            if any(k in nm for k in kinds):
                v = eqn.invars[0].aval
                found.append((nm, tuple(v.shape), str(v.dtype)))
            for p in eqn.params.values():
                ps = p if isinstance(p, (list, tuple)) else [p]
                for j in ps:
                    if hasattr(j, "jaxpr"):
                        walk(j.jaxpr)
                    elif hasattr(j, "eqns"):
                        walk(j)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


# ---------------------------------------------------------------------------
# Block-local pieces: transfer operator, boundary propagation, local scan.
# ---------------------------------------------------------------------------

def _shift_rows_down(t):
    """t[..., j, :] -> t[..., j-1, :]; row 0 becomes 0."""
    pad = [(0, 0)] * (t.ndim - 2) + [(1, 0), (0, 0)]
    return jnp.pad(t, pad)[..., :-1, :]


def _shift_rows_up(t):
    """t[..., j, :] -> t[..., j+1, :]; last row becomes 0."""
    pad = [(0, 0)] * (t.ndim - 2) + [(0, 1), (0, 0)]
    return jnp.pad(t, pad)[..., 1:, :]


def block_transfer_operator(wl, wc, wr, *, reverse: bool = False):
    """T_k = ∏ M[r] over the block's rows, composed in scan order.

    wl/wc/wr: (G_w, H_blk, W).  Returns (G_w, W, W) f32 mapping the
    incoming boundary column to the outgoing one.  ``reverse=True``
    composes bottom→top (the reverse-direction scan's operator).
    """
    gw, _, w = wl.shape

    def body(t, row):
        wl_r, wc_r, wr_r = (a.astype(jnp.float32)[..., None] for a in row)
        # (M t)[j, c] = wl[j] t[j-1, c] + wc[j] t[j, c] + wr[j] t[j+1, c]
        t = wl_r * _shift_rows_down(t) + wc_r * t + wr_r * _shift_rows_up(t)
        return t, None

    eye = jnp.broadcast_to(jnp.eye(w, dtype=jnp.float32), (gw, w, w))
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (wl, wc, wr))
    t, _ = jax.lax.scan(body, eye, xs, reverse=reverse)
    return t


def block_boundary_states(x, wl, wc, wr, lam, *, reverse: bool = False):
    """The block's complete exchange payload ``(T_k, bl_k)`` in ONE cheap
    affine scan — no full-width local scan needed.

    The recurrence is linear in the carry, so the pair (operator, state)

        T ← M[r] T                      (the (W, W) transfer operator)
        b ← M[r] b + lam[r]·x[r]        (the zero-state local boundary)

    composes jointly row by row; after the block's rows, ``T = ∏ M[r]``
    equals :func:`block_transfer_operator` and ``b`` equals the local
    scan's outgoing boundary row (``h_loc[:, -1]``, or ``h_loc[:, 0]``
    for ``reverse=True``).  Computing the payload this way is what lets
    the fused-pair path ISSUE its collective before the expensive local
    scan runs (DESIGN.md §8).

    x, lam: (G, H_blk, W); taps (G_w, H_blk, W).  Returns
    ``(t (G_w, W, W) f32, b (G, W) f32)``.
    """
    gw, _, w = wl.shape
    g = x.shape[0]
    cpw = g // gw

    def body(carry, row):
        t, b = carry
        wl_r, wc_r, wr_r, u_r = row
        wl_m, wc_m, wr_m = (a[..., None] for a in (wl_r, wc_r, wr_r))
        t = wl_m * _shift_rows_down(t) + wc_m * t + wr_m * _shift_rows_up(t)
        bg = b.reshape(gw, cpw, w)
        wl_c, wc_c, wr_c = (a[:, None, :] for a in (wl_r, wc_r, wr_r))
        bg = (wl_c * _ref._shift_right(bg) + wc_c * bg
              + wr_c * _ref._shift_left(bg))
        b = bg.reshape(g, w) + u_r
        return (t, b), None

    eye = jnp.broadcast_to(jnp.eye(w, dtype=jnp.float32), (gw, w, w))
    zero = jnp.zeros((g, w), jnp.float32)
    xs = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0)
               for a in (wl, wc, wr)) + (
        jnp.moveaxis(lam.astype(jnp.float32) * x.astype(jnp.float32), 1, 0),)
    (t, b), _ = jax.lax.scan(body, (eye, zero), xs, reverse=reverse)
    return t, b


def _apply_transfer(t, b, cpw: int):
    """t: (G_w, W, W) acting on boundary columns b: (G, W), G = G_w·cpw."""
    gw = t.shape[0]
    bg = b.reshape(gw, cpw, b.shape[-1])
    return jnp.einsum("gjk,gck->gcj", t, bg).reshape(b.shape)


def propagate_boundary(b, wl, wc, wr, *, reverse: bool = False):
    """Carry a boundary column homogeneously through the block.

    b: (G, W); taps (G_w, H_blk, W).  Returns (G, H_blk, W) f32 where row
    i holds (∏_{entry..i} M[r]) b — exactly the correction each local row
    needs once the true incoming boundary is known.  Cost matches one
    local scan minus the lam·x term; no (W, W) operator is materialised.
    """
    g = b.shape[0]
    wl = _ref._broadcast_w(wl, g)
    wc = _ref._broadcast_w(wc, g)
    wr = _ref._broadcast_w(wr, g)

    def body(h, row):
        wl_r, wc_r, wr_r = row
        h = (wl_r * _ref._shift_right(h) + wc_r * h
             + wr_r * _ref._shift_left(h))
        return h, h

    xs = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0)
               for a in (wl, wc, wr))
    _, cs = jax.lax.scan(body, b.astype(jnp.float32), xs, reverse=reverse)
    return jnp.moveaxis(cs, 0, 1)


def _local_scan(cfg: SPConfig, x, wl, wc, wr, lam, *, reverse: bool):
    """Block-local scan with zero incoming state (the existing kernels)."""
    if not reverse and cfg.spec.impl == "pallas":
        return _pk.gspn_scan_fwd_pallas(x, wl, wc, wr, lam, spec=cfg.spec)
    # Reverse-direction local scans (the adjoint pass) go through the XLA
    # fused-scan oracle — same recurrence, reversed row walk.
    return _ref.gspn_scan_ref(x, wl, wc, wr, lam, reverse=reverse)


# ---------------------------------------------------------------------------
# The single logical boundary exchange.
# ---------------------------------------------------------------------------

def _exchange(t, b_last, cfg: SPConfig, *, reverse: bool):
    """Compose block boundaries across the ``seq`` axis.

    t: (G_w, W, W) local transfer operator; b_last: (G, W) local
    uncorrected outgoing boundary.  Returns the corrected INCOMING
    boundary for this block — zeros for the first block in scan order.
    This is the only cross-device communication of the scan: one logical
    exchange of boundary columns (never full activations).
    """
    k, ax, cpw = cfg.n_blocks, cfg.axis_name, cfg.channels_per_weight
    zero = jnp.zeros_like(b_last, dtype=jnp.float32)
    if k == 1:
        return zero
    wire = jnp.dtype(cfg.boundary_dtype)
    b_last = b_last.astype(jnp.float32)
    idx = jax.lax.axis_index(ax)
    # Position in scan order: the reverse pass consumes blocks last→first.
    pos = (k - 1 - idx) if reverse else idx

    if cfg.resolved_strategy() == "ppermute":
        # Neighbour chain: K-1 hops, each forwarding one boundary column.
        # At hop s the block at scan position s-1 (whose incoming boundary
        # was finalised at hop s-1) sends its corrected outgoing boundary
        # T·b_in + b_last to position s; everyone else's payload is
        # ignored by the masked update.  The payload crosses the wire in
        # cfg.boundary_dtype; the fold stays f32 (DESIGN.md §10).
        perm = ([(i, i - 1) for i in range(1, k)] if reverse
                else [(i, i + 1) for i in range(k - 1)])
        b_in = zero
        for s in range(1, k):
            # Only scan position s-1's payload is consumed at hop s: mask
            # the rest to zero so every other device ships a constant
            # instead of a fresh T·b_in + b_last matvec, and a narrow wire
            # dtype only ever quantizes the chain actually consumed.
            send = jnp.where(pos == s - 1,
                             _apply_transfer(t, b_in, cpw) + b_last,
                             zero).astype(wire)
            recv = jax.lax.ppermute(send, ax, perm).astype(jnp.float32)
            b_in = jnp.where(pos == s, recv, b_in)
        return b_in

    # allgather: ONE log-depth collective of the compact (T, b) pairs;
    # each device then folds its own prefix with K cheap matvecs (the
    # composition (T_b, b_b)∘(T_a, b_a) = (T_b T_a, T_b b_a + b_b) applied
    # left-to-right in scan order — no (W, W) matmuls needed since only
    # the boundary column, not the composed operator, is consumed).  The
    # gathered (T, b) payloads cross the wire in cfg.boundary_dtype; the
    # prefix fold composes in f32.
    tg = jax.lax.all_gather(t.astype(wire), ax)   # (K, G_w, W, W) dev order
    bg = jax.lax.all_gather(b_last.astype(wire), ax)    # (K, G, W)
    if reverse:
        tg, bg = jnp.flip(tg, 0), jnp.flip(bg, 0)   # reorder to scan order

    def fold(acc, pair):
        tj, bj = pair
        nxt = _apply_transfer(tj.astype(jnp.float32), acc, cpw) \
            + bj.astype(jnp.float32)
        return nxt, nxt

    _, prefixes = jax.lax.scan(fold, zero, (tg, bg))
    # prefixes[p] is the incoming boundary of scan position p+1.
    prefixes = jnp.concatenate([zero[None], prefixes[:-1]], axis=0)
    return jnp.take(prefixes, pos, axis=0)


def _block_scan(cfg: SPConfig, x, wl, wc, wr, lam, *, reverse: bool):
    """One block-parallel scan pass (shard-local; collectives inside).

    Returns (h, b_in): globally-corrected outputs for the local rows
    (f32) and the corrected incoming boundary (f32, (G, W)).

    The four phases are wrapped in ``jax.named_scope`` so the XLA
    profiler timeline aligns with the span names (DESIGN.md §13).
    """
    with jax.named_scope("sp.local_scan"):
        h_loc = _local_scan(cfg, x, wl, wc, wr, lam,
                            reverse=reverse).astype(jnp.float32)
    b_last = h_loc[:, 0, :] if reverse else h_loc[:, -1, :]
    with jax.named_scope("sp.transfer_operator"):
        t = block_transfer_operator(wl, wc, wr, reverse=reverse)
    with jax.named_scope("gspn_sp.exchange"):
        b_in = _exchange(t, b_last, cfg, reverse=reverse)
    with jax.named_scope("sp.correction"):
        h = h_loc + propagate_boundary(b_in, wl, wc, wr, reverse=reverse)
    return h, b_in


# ---------------------------------------------------------------------------
# custom_vjp core (runs inside shard_map).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sp_core(cfg: SPConfig, x, wl, wc, wr, lam):
    h, _ = _block_scan(cfg, x, wl, wc, wr, lam, reverse=False)
    return h.astype(x.dtype)


def _sp_core_fwd(cfg, x, wl, wc, wr, lam):
    h, b_in = _block_scan(cfg, x, wl, wc, wr, lam, reverse=False)
    return h.astype(x.dtype), (x, wl, wc, wr, lam, h, b_in)


def _sp_core_bwd(cfg, res, dy):
    x, wl, wc, wr, lam, h, b_in = res            # h, b_in already f32
    k, ax = cfg.n_blocks, cfg.axis_name
    wl32, wc32, wr32 = (a.astype(jnp.float32) for a in (wl, wc, wr))

    # Adjoint taps at row i are row i+1's weights; the last local row's
    # successor lives on the right neighbour — fetch its first weight row
    # (one single-row ppermute; the exchange direction is reversed, as is
    # the boundary composition below).  The globally-last block receives
    # zeros: g[H-1] = dy[H-1].
    w_first = jnp.stack([wl32[:, 0], wc32[:, 0], wr32[:, 0]])
    if k > 1:
        w_first = jax.lax.ppermute(
            w_first, ax, [(i + 1, i) for i in range(k - 1)])
    else:
        w_first = jnp.zeros_like(w_first)

    def rows_next(a, first_next):
        return jnp.concatenate([a[:, 1:], first_next[:, None]], axis=1)

    wl_n = rows_next(wl32, w_first[0])
    wc_n = rows_next(wc32, w_first[1])
    wr_n = rows_next(wr32, w_first[2])
    # Transposed tridiagonal: g[i,j] = dy + wr[i+1,j-1]·g[i+1,j-1]
    #                + wc[i+1,j]·g[i+1,j] + wl[i+1,j+1]·g[i+1,j+1].
    wl_adj = _ref._shift_right(wr_n)
    wc_adj = wc_n
    wr_adj = _ref._shift_left(wl_n)

    dy32 = dy.astype(jnp.float32)
    g, _ = _block_scan(cfg, dy32, wl_adj, wc_adj, wr_adj,
                       jnp.ones_like(dy32), reverse=True)

    # Parameter/input grads are local given g and the previous-row states;
    # the block's first row reads the forward incoming boundary.
    h_prev = jnp.concatenate([b_in[:, None], h[:, :-1]], axis=1)
    dx = (lam.astype(jnp.float32) * g).astype(x.dtype)
    dlam = (x.astype(jnp.float32) * g).astype(lam.dtype)
    dwl = g * _ref._shift_right(h_prev)
    dwc = g * h_prev
    dwr = g * _ref._shift_left(h_prev)
    cpw = cfg.channels_per_weight
    if cpw > 1:
        gw = x.shape[0] // cpw
        shp = (gw, cpw) + dwl.shape[1:]
        dwl = dwl.reshape(shp).sum(axis=1)
        dwc = dwc.reshape(shp).sum(axis=1)
        dwr = dwr.reshape(shp).sum(axis=1)
    return (dx, dwl.astype(wl.dtype), dwc.astype(wc.dtype),
            dwr.astype(wr.dtype), dlam)


_sp_core.defvjp(_sp_core_fwd, _sp_core_bwd)


# ---------------------------------------------------------------------------
# Fused opposite-direction pair: ONE collective, compute/comm overlap.
# ---------------------------------------------------------------------------

def _pair_payload_parts(gw: int, g: int, w: int, *, with_edges: bool):
    """Row extents of the packed per-direction payload (P axis)."""
    return gw * w, g, (3 * gw if with_edges else 0)


def _issue_pair_exchange(cfg: SPConfig, t2, b2, edge2):
    """Pack both directions' compact states into ONE array and all-gather.

    t2: (2, G_w, W, W); b2: (2, G, W); edge2: (2, 3, G_w, W) adjoint edge
    weight rows (or None on the backward pass, which needs none).  The
    packed payload is (2, P, W) with P = G_w·W + G [+ 3·G_w]; it crosses
    the wire in ``cfg.boundary_dtype``.  Returns the gathered (K, 2, P,
    W) array, or None when the exchange is skipped (timing floor).
    """
    if cfg.exchange_mode == "skip":
        return None
    _, gw, w, _ = t2.shape
    parts = [t2.reshape(2, gw * w, w), b2]
    if edge2 is not None:
        parts.append(edge2.reshape(2, 3 * gw, w))
    payload = jnp.concatenate(parts, axis=1).astype(
        jnp.dtype(cfg.boundary_dtype))
    with jax.named_scope("gspn_sp.exchange"):
        return jax.lax.all_gather(payload, cfg.axis_name)


def _fold_pair_exchange(cfg: SPConfig, gathered, gw, g, w, *,
                        with_edges: bool):
    """Unpack the gathered pair payload and fold each direction's prefix.

    Slot 0 scans in device order (scan position = idx), slot 1 in
    reversed device order.  Returns ``b_in2`` (2, G, W) f32 — each
    direction's corrected incoming boundary — plus, when ``with_edges``,
    the adjoint edge weight rows: ``w_next0`` (3, G_w, W) = the RIGHT
    neighbour's first dir-0 rows and ``w_prev1`` = the LEFT neighbour's
    last dir-1 rows (zeros at the respective grid edges).
    """
    k, ax, cpw = cfg.n_blocks, cfg.axis_name, cfg.channels_per_weight
    zero = jnp.zeros((g, w), jnp.float32)
    if gathered is None:
        b_in2 = jnp.stack([zero, zero])
        if not with_edges:
            return b_in2
        ez = jnp.zeros((3, gw, w), jnp.float32)
        return b_in2, ez, ez
    f32 = gathered.astype(jnp.float32)             # (K, 2, P, W)
    tg = f32[:, :, :gw * w, :].reshape(k, 2, gw, w, w)
    bg = f32[:, :, gw * w:gw * w + g, :]
    idx = jax.lax.axis_index(ax)

    def prefix(ts, bs, pos):
        def fold(acc, pair):
            tj, bj = pair
            nxt = _apply_transfer(tj, acc, cpw) + bj
            return nxt, nxt
        _, pre = jax.lax.scan(fold, zero, (ts, bs))
        pre = jnp.concatenate([zero[None], pre[:-1]], axis=0)
        return jnp.take(pre, pos, axis=0)

    b_in2 = jnp.stack([
        prefix(tg[:, 0], bg[:, 0], idx),
        prefix(jnp.flip(tg[:, 1], 0), jnp.flip(bg[:, 1], 0), k - 1 - idx),
    ])
    if not with_edges:
        return b_in2
    eg = f32[:, :, gw * w + g:, :].reshape(k, 2, 3, gw, w)
    w_next0 = jnp.where(
        idx < k - 1, jnp.take(eg[:, 0], jnp.minimum(idx + 1, k - 1), axis=0),
        0.0)
    w_prev1 = jnp.where(
        idx > 0, jnp.take(eg[:, 1], jnp.maximum(idx - 1, 0), axis=0), 0.0)
    return b_in2, w_next0, w_prev1


def _local_scan_pair(cfg: SPConfig, x, wl2, wc2, wr2, lam2):
    """Block-local opposite-direction pair scan with zero incoming state."""
    if cfg.spec.impl == "multidir":
        from repro.kernels import gspn_multidir as _mk
        out = _mk.gspn_scan_bidir_pallas(
            x, {"wl": wl2, "wc": wc2, "wr": wr2}, lam2, spec=cfg.spec)
        return out.astype(jnp.float32)
    fwd = _ref.gspn_scan_ref(x, wl2[0], wc2[0], wr2[0], lam2[0])
    rev = _ref.gspn_scan_ref(x, wl2[1], wc2[1], wr2[1], lam2[1],
                             reverse=True)
    return jnp.stack([fwd, rev]).astype(jnp.float32)


def _pair_forward(cfg: SPConfig, x, wl2, wc2, wr2, lam2):
    """The fused-pair forward (shard-local).  Phase order is the point:

      1. ``sp.boundary_states`` — cheap affine (T, b) scans, BOTH
         directions, producing the full exchange payload;
      2. ``sp.exchange``        — the ONE all-gather, issued now;
      3. ``sp.local_scan``      — the expensive block-local pair scan,
         data-independent of the gather → overlaps it;
      4. ``sp.fold`` / ``sp.correction`` — the only consumers of the
         gathered bytes.

    Returns ``(h2 (2, G, H_blk, W) f32, b_in2, w_next0, w_prev1)``; the
    edge rows ride the same collective for the backward pass, replacing
    the per-direction path's extra single-row ppermute.
    """
    gw = wl2.shape[1]
    g, _, w = x.shape
    x32 = x.astype(jnp.float32)
    lam32 = lam2.astype(jnp.float32)
    wl2_, wc2_, wr2_ = (a.astype(jnp.float32) for a in (wl2, wc2, wr2))

    with jax.named_scope("sp.boundary_states"):
        t0, b0 = block_boundary_states(x32, wl2_[0], wc2_[0], wr2_[0],
                                       lam32[0])
        t1, b1 = block_boundary_states(x32, wl2_[1], wc2_[1], wr2_[1],
                                       lam32[1], reverse=True)
        edge2 = jnp.stack([
            jnp.stack([wl2_[0][:, 0], wc2_[0][:, 0], wr2_[0][:, 0]]),
            jnp.stack([wl2_[1][:, -1], wc2_[1][:, -1], wr2_[1][:, -1]]),
        ])
    gathered = _issue_pair_exchange(cfg, jnp.stack([t0, t1]),
                                    jnp.stack([b0, b1]), edge2)

    if cfg.exchange_mode == "serial" and gathered is not None:
        # Exposed-exchange baseline for the overlap rung: pin the gather
        # onto the critical path ahead of the local scan.
        gathered, x32 = jax.lax.optimization_barrier((gathered, x32))

    with jax.named_scope("sp.local_scan"):
        h_loc2 = _local_scan_pair(cfg, x32, wl2_, wc2_, wr2_, lam32)

    with jax.named_scope("sp.fold"):
        b_in2, w_next0, w_prev1 = _fold_pair_exchange(
            cfg, gathered, gw, g, w, with_edges=True)

    with jax.named_scope("sp.correction"):
        h2 = jnp.stack([
            h_loc2[0] + propagate_boundary(b_in2[0], wl2_[0], wc2_[0],
                                           wr2_[0]),
            h_loc2[1] + propagate_boundary(b_in2[1], wl2_[1], wc2_[1],
                                           wr2_[1], reverse=True),
        ])
    return h2, b_in2, w_next0, w_prev1


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sp_pair_core(cfg: SPConfig, x, wl2, wc2, wr2, lam2):
    h2, _, _, _ = _pair_forward(cfg, x, wl2, wc2, wr2, lam2)
    return h2.astype(x.dtype)


def _sp_pair_core_fwd(cfg, x, wl2, wc2, wr2, lam2):
    h2, b_in2, w_next0, w_prev1 = _pair_forward(cfg, x, wl2, wc2, wr2, lam2)
    return h2.astype(x.dtype), (x, wl2, wc2, wr2, lam2, h2, b_in2,
                                w_next0, w_prev1)


def _sp_pair_core_bwd(cfg, res, dy2):
    """Adjoint of the fused pair — itself an opposite pair, so it too is
    ONE fused exchange: dir 1's adjoint scans forward (fwd slot), dir 0's
    scans in reverse.  The neighbour edge weight rows arrived on the
    FORWARD's collective (residuals), so no ppermute remains anywhere."""
    x, wl2, wc2, wr2, lam2, h2, b_in2, w_next0, w_prev1 = res
    gw = wl2.shape[1]
    g, _, w = x.shape
    wl2_, wc2_, wr2_ = (a.astype(jnp.float32) for a in (wl2, wc2, wr2))
    dy32 = dy2.astype(jnp.float32)
    ones = jnp.ones_like(dy32[0])

    # Adjoint taps: the transposed tridiagonal of the NEXT row in each
    # direction's scan order — dir 0's row-(i+1) weights (successor of
    # the block's last row = right neighbour's first, w_next0), dir 1's
    # row-(i-1) weights (left neighbour's last, w_prev1).
    def rows_next(a, nxt):
        return jnp.concatenate([a[:, 1:], nxt[:, None]], axis=1)

    def rows_prev(a, prv):
        return jnp.concatenate([prv[:, None], a[:, :-1]], axis=1)

    wl0n, wc0n, wr0n = (rows_next(a, e) for a, e in
                        zip((wl2_[0], wc2_[0], wr2_[0]), w_next0))
    a0 = (_ref._shift_right(wr0n), wc0n, _ref._shift_left(wl0n))
    wl1p, wc1p, wr1p = (rows_prev(a, e) for a, e in
                        zip((wl2_[1], wc2_[1], wr2_[1]), w_prev1))
    a1 = (_ref._shift_right(wr1p), wc1p, _ref._shift_left(wl1p))

    with jax.named_scope("sp.bwd.boundary_states"):
        t1a, b1a = block_boundary_states(dy32[1], *a1, ones)
        t0a, b0a = block_boundary_states(dy32[0], *a0, ones, reverse=True)
    gathered = _issue_pair_exchange(cfg, jnp.stack([t1a, t0a]),
                                    jnp.stack([b1a, b0a]), None)
    with jax.named_scope("sp.bwd.local_scan"):
        g1 = _ref.gspn_scan_ref(dy32[1], *a1, ones)
        g0 = _ref.gspn_scan_ref(dy32[0], *a0, ones, reverse=True)
    with jax.named_scope("sp.bwd.fold"):
        g_in2 = _fold_pair_exchange(cfg, gathered, gw, g, w,
                                    with_edges=False)
    with jax.named_scope("sp.bwd.correction"):
        g1 = g1.astype(jnp.float32) + propagate_boundary(g_in2[0], *a1)
        g0 = g0.astype(jnp.float32) + propagate_boundary(g_in2[1], *a0,
                                                         reverse=True)

    # Param/input grads are local given g and the previous-row states;
    # each direction's first row (in its own scan order) reads the saved
    # forward incoming boundary.
    x32 = x.astype(jnp.float32)
    lam32 = lam2.astype(jnp.float32)
    g2 = jnp.stack([g0, g1])
    hp2 = jnp.stack([
        jnp.concatenate([b_in2[0][:, None], h2[0][:, :-1]], axis=1),
        jnp.concatenate([h2[1][:, 1:], b_in2[1][:, None]], axis=1),
    ])
    dx = (lam32[0] * g0 + lam32[1] * g1).astype(x.dtype)
    dlam2 = (x32[None] * g2).astype(lam2.dtype)
    dwl = g2 * _ref._shift_right(hp2)
    dwc = g2 * hp2
    dwr = g2 * _ref._shift_left(hp2)
    cpw = cfg.channels_per_weight
    if cpw > 1:
        shp = (2, g // cpw, cpw) + dwl.shape[2:]
        dwl = dwl.reshape(shp).sum(axis=2)
        dwc = dwc.reshape(shp).sum(axis=2)
        dwr = dwr.reshape(shp).sum(axis=2)
    return (dx, dwl.astype(wl2.dtype), dwc.astype(wc2.dtype),
            dwr.astype(wr2.dtype), dlam2)


_sp_pair_core.defvjp(_sp_pair_core_fwd, _sp_pair_core_bwd)


# ---------------------------------------------------------------------------
# Public entry point.
# ---------------------------------------------------------------------------

def gspn_scan_sp(x, wl, wc, wr, lam, *, spec: ScanSpec | None = None,
                 mesh=None, axis_name: str = "seq",
                 strategy: str = "auto", inner_impl: str = "auto",
                 row_tile: int | None = None, interpret: bool | None = None,
                 chunk: int | None = None, batch_axes=None,
                 boundary_dtype=None, carry_dtype=None,
                 pipeline_depth: int | None = None):
    """Spatially-sharded GSPN line scan (``impl="sp"``).

    Same semantics and layout as :func:`repro.kernels.ops.gspn_scan` —
    x, lam: (G, H, W); wl/wc/wr: (G_w, H, W) — but the scan dimension H is
    partitioned into contiguous blocks over the ``axis_name`` mesh axis.
    Launch policy arrives as one :class:`ScanSpec` (``spec=``); the
    legacy loose kwargs (``inner_impl``/``row_tile``/``interpret``/
    ``carry_dtype``/``pipeline_depth``) remain accepted when no spec is
    given and are folded into one.  The block-local launch runs under
    ``spec.with_(boundary="sp_block_local", impl=<resolved inner>)``.
    ``boundary_dtype`` (default f32) is the wire dtype of the boundary
    exchange payloads; composition always runs in f32 (DESIGN.md §10).
    The spec's carry dtype follows the active precision policy rather
    than a hard-coded f32 so the tuner keys the block-local launch
    correctly (DESIGN.md §11).
    Differentiable in all tensor args (custom_vjp; the backward pass
    reverses the exchange direction).  H need not divide the axis size.

    On meshes that also carry data-parallel axes, the G dim stays
    distributed over them (``batch_axes``, default: whichever of
    ``("pod", "data")`` the mesh has, when they divide G and G_w) — the
    scan is batch-parallel, so replicating G would force the partitioner
    to all-gather activations at every layer.

    Falls back to the single-device fused path when no mesh / no
    ``axis_name`` axis / axis size 1, and for GSPN-local chunked scans
    (``chunk`` resets the carry per segment, so the chunked fused path is
    already parallel over segments and exchanges no boundary state);
    ``impl="sp"`` is therefore safe to set unconditionally in configs,
    but combining it with ``chunk`` yields no cross-device memory saving.
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown sp strategy {strategy!r}")
    if spec is None:
        spec = ScanSpec(
            impl=inner_impl, row_tile=row_tile, interpret=interpret,
            carry_dtype=str(jnp.dtype(carry_dtype if carry_dtype is not None
                                      else jnp.float32)),
            pipeline_depth=pipeline_depth)
    mesh = mesh if mesh is not None else compat.ambient_mesh()
    n_seq = (mesh.shape[axis_name]
             if mesh is not None and axis_name in mesh.axis_names else 1)
    if n_seq == 1 or chunk is not None:
        # GSPN-local chunking resets the carry at segment entry — there is
        # no cross-block state to exchange, so the chunked fused path is
        # already embarrassingly parallel and sp adds nothing to it.
        from repro.kernels.ops import gspn_scan
        return gspn_scan(x, wl, wc, wr, lam, chunk=chunk,
                         spec=spec.with_(impl="auto", boundary="one_shot"))

    g, h_dim, w = x.shape
    gw = wl.shape[0]
    assert g % gw == 0, (g, gw)
    h_blk = -(-h_dim // n_seq)
    pad = h_blk * n_seq - h_dim
    if pad:
        # Zero rows at the scan end: zero taps/lam keep them exactly zero
        # through forward and adjoint, and real boundaries never cross them.
        def pad_rows(a):
            return jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        x, wl, wc, wr, lam = map(pad_rows, (x, wl, wc, wr, lam))

    # ``impl="sp"`` at this layer means "the sp wrapper itself" — the
    # block-local kernel choice falls back to auto resolution.
    inner = _resolve_inner("auto" if spec.impl in ("auto", "sp")
                           else spec.impl)
    cfg = SPConfig(axis_name=axis_name, n_blocks=n_seq, strategy=strategy,
                   boundary_dtype=str(jnp.dtype(
                       boundary_dtype if boundary_dtype is not None
                       else jnp.float32)),
                   spec=spec.with_(direction="fwd", impl=inner,
                                   channels_per_weight=g // gw,
                                   stream_dtype=str(jnp.dtype(x.dtype)),
                                   boundary="sp_block_local"))
    # Traced-launch accounting of the one boundary exchange (DESIGN.md
    # §13): analytic per-scan byte counts, recorded once per jit TRACE of
    # this call site (jit caching means executed steps reuse the trace).
    # activation_bytes is what a naive full-activation collective would
    # move — the traffic the compact exchange avoids.
    wire_bytes = jnp.dtype(cfg.boundary_dtype).itemsize
    if cfg.resolved_strategy() == "ppermute":
        n_ops = n_seq - 1
        boundary_bytes = (n_seq - 1) * g * w * wire_bytes
    else:
        n_ops = 1
        boundary_bytes = n_seq * (gw * w * w + g * w) * wire_bytes
    act_bytes = x.size * jnp.dtype(x.dtype).itemsize
    obs.counter("sp_exchanges_total").inc()
    obs.counter("sp_collective_ops_total").inc(n_ops)
    obs.counter("sp_boundary_bytes_total").inc(boundary_bytes)
    obs.counter("sp_activation_bytes_total").inc(act_bytes)
    obs.event("sp.exchange", strategy=cfg.resolved_strategy(),
              n_blocks=n_seq, collective_ops=n_ops,
              boundary_bytes=boundary_bytes, activation_bytes=act_bytes,
              wire_dtype=cfg.boundary_dtype)
    # Shard G over dp only when both G and G_w divide: G is grouped
    # (G_w, cpw)-contiguously, and gw % bsize == 0 keeps every weight
    # group whole within its shard.
    bspec = _dp_batch_spec(mesh, batch_axes, axis_name, g, gw)
    pspec = P(bspec, axis_name, None)
    out = compat.shard_map(
        functools.partial(_sp_core, cfg), mesh=mesh,
        in_specs=(pspec,) * 5, out_specs=pspec,
    )(x, wl, wc, wr, lam)
    return out[:, :h_dim] if pad else out


def _dp_batch_spec(mesh, batch_axes, axis_name, g, gw):
    """The G-dim partition entry shared by both sp entry points."""
    if batch_axes is None:
        batch_axes = ("pod", "data")
    batch_axes = tuple(a for a in batch_axes
                       if a in mesh.axis_names and a != axis_name)
    bsize = 1
    for a in batch_axes:
        bsize *= mesh.shape[a]
    if bsize > 1 and g % bsize == 0 and gw % bsize == 0:
        return batch_axes if len(batch_axes) > 1 else batch_axes[0]
    return None


def gspn_scan_sp_pair(x, wl2, wc2, wr2, lam2, *, spec: ScanSpec | None = None,
                      mesh=None, axis_name: str = "seq",
                      strategy: str = "auto", inner_impl: str = "auto",
                      row_tile: int | None = None,
                      interpret: bool | None = None,
                      chunk: int | None = None, batch_axes=None,
                      boundary_dtype=None, carry_dtype=None,
                      pipeline_depth: int | None = None,
                      exchange_mode: str = "overlap"):
    """Spatially-sharded fused opposite-direction pair (``impl="sp"``).

    Layout matches :func:`repro.kernels.ops.gspn_scan_pair`: one shared
    stream ``x`` (G, H, W); per-direction taps ``wl2/wc2/wr2``
    (2, G_w, H, W) and ``lam2`` (2, G, H, W), slot 0 scanning top→bottom
    and slot 1 bottom→top.  Under the default/auto strategy the two
    directions share ONE boundary collective — a single all-gather of the
    stacked compact ``(T, b)`` states, issued before the block-local pair
    scan so the exchange overlaps the compute (module docstring; jaxpr
    pin: 1 collective forward, 2 in the gradient, 0 ppermutes).  Forcing
    ``strategy="ppermute"``/``"allgather"`` keeps the pre-fusion
    per-direction behaviour (two independent exchanges) as a fallback
    knob.  ``exchange_mode`` ∈ ``EXCHANGE_MODES`` is the overlap-rung
    measurement knob; anything but ``"overlap"`` is for benchmarking
    only.  Differentiable in all tensor args (custom_vjp; the backward is
    the mirrored pair with its own single fused exchange).
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown sp strategy {strategy!r}")
    if exchange_mode not in EXCHANGE_MODES:
        raise ValueError(f"unknown sp exchange mode {exchange_mode!r}")
    if spec is None:
        spec = ScanSpec(
            impl=inner_impl, row_tile=row_tile, interpret=interpret,
            carry_dtype=str(jnp.dtype(carry_dtype if carry_dtype is not None
                                      else jnp.float32)),
            pipeline_depth=pipeline_depth)
    mesh = mesh if mesh is not None else compat.ambient_mesh()
    n_seq = (mesh.shape[axis_name]
             if mesh is not None and axis_name in mesh.axis_names else 1)
    if n_seq == 1 or chunk is not None:
        from repro.kernels.ops import gspn_scan_pair
        return gspn_scan_pair(
            x, wl2, wc2, wr2, lam2, chunk=chunk,
            spec=spec.with_(impl="auto", direction="pair_fwd",
                            boundary="one_shot"))

    if SPConfig(n_blocks=n_seq, strategy=strategy).resolved_strategy(
            pair=True) != "pair_allgather":
        # Per-direction fallback knob: two independent exchanges, exactly
        # the pre-fusion behaviour.  Slot 1 runs through the flip
        # identity (a reverse scan is a data reversal of a forward one).
        def flip(a):
            return jnp.flip(a, axis=-2)

        kw = dict(spec=spec, mesh=mesh, axis_name=axis_name,
                  strategy=strategy, batch_axes=batch_axes,
                  boundary_dtype=boundary_dtype)
        out0 = gspn_scan_sp(x, wl2[0], wc2[0], wr2[0], lam2[0], **kw)
        out1 = flip(gspn_scan_sp(flip(x), flip(wl2[1]), flip(wc2[1]),
                                 flip(wr2[1]), flip(lam2[1]), **kw))
        return jnp.stack([out0, out1])

    g, h_dim, w = x.shape
    gw = wl2.shape[1]
    assert g % gw == 0, (g, gw)
    h_blk = -(-h_dim // n_seq)
    pad = h_blk * n_seq - h_dim
    if pad:
        # Zero rows at the ARRAY end: zero taps/lam keep them exactly
        # zero in both directions (slot 1 enters through them with a
        # zero carry — the same state the unpadded scan starts from).
        def pad_rows(a):
            width = ((0, 0),) * (a.ndim - 2) + ((0, pad), (0, 0))
            return jnp.pad(a, width)
        x, wl2, wc2, wr2, lam2 = (pad_rows(a)
                                  for a in (x, wl2, wc2, wr2, lam2))

    inner = _resolve_inner_pair("auto" if spec.impl in ("auto", "sp")
                                else spec.impl)
    cfg = SPConfig(axis_name=axis_name, n_blocks=n_seq, strategy=strategy,
                   boundary_dtype=str(jnp.dtype(
                       boundary_dtype if boundary_dtype is not None
                       else jnp.float32)),
                   exchange_mode=exchange_mode,
                   spec=spec.with_(direction="pair_fwd", impl=inner,
                                   channels_per_weight=g // gw,
                                   stream_dtype=str(jnp.dtype(x.dtype)),
                                   boundary="sp_block_local"))
    wire_bytes = jnp.dtype(cfg.boundary_dtype).itemsize
    n_ops = 0 if exchange_mode == "skip" else 1
    payload_rows = sum(_pair_payload_parts(gw, g, w, with_edges=True))
    boundary_bytes = n_ops * n_seq * 2 * payload_rows * w * wire_bytes
    act_bytes = 2 * x.size * jnp.dtype(x.dtype).itemsize
    obs.counter("sp_exchanges_total").inc()
    obs.counter("sp_pair_fused_exchanges_total").inc()
    obs.counter("sp_collective_ops_total").inc(n_ops)
    obs.counter("sp_boundary_bytes_total").inc(boundary_bytes)
    obs.counter("sp_activation_bytes_total").inc(act_bytes)
    obs.event("sp.exchange", strategy="pair_allgather", fused_pair=True,
              n_blocks=n_seq, collective_ops=n_ops,
              boundary_bytes=boundary_bytes, activation_bytes=act_bytes,
              wire_dtype=cfg.boundary_dtype, exchange_mode=exchange_mode)

    bspec = _dp_batch_spec(mesh, batch_axes, axis_name, g, gw)
    pspec = P(bspec, axis_name, None)
    pspec2 = P(None, bspec, axis_name, None)
    out = compat.shard_map(
        functools.partial(_sp_pair_core, cfg), mesh=mesh,
        in_specs=(pspec, pspec2, pspec2, pspec2, pspec2), out_specs=pspec2,
    )(x, wl2, wc2, wr2, lam2)
    return out[:, :, :h_dim] if pad else out
