"""Jit-friendly public ops for the GSPN-2 line scan.

Two ``custom_vjp`` primitive-like entry points with hand-derived adjoint
scans (DESIGN.md §2) are used by ``repro.core.gspn``:

* ``gspn_scan``      — one directional line scan (G, H, W) -> (G, H, W);
* ``gspn_scan_pair`` — one OPPOSITE-DIRECTION PAIR in a single fused
  launch: the canonical top→bottom scan and its bottom→top mirror share
  every ``x`` tile, so a full four-direction GSPN pass costs two launches
  instead of four (see ``repro.core.gspn.directional_scan``).

The impl matrix (both entry points):

* ``impl="pallas"``  — the fused Pallas TPU kernel (compiled by Mosaic
  when lowered for a TPU; the Pallas interpreter on every other backend,
  which is how the CPU tests validate it);
* ``impl="multidir"``— the fused opposite-pair Pallas kernel
  (``kernels/gspn_multidir.py``); for the single-direction ``gspn_scan``
  this degenerates to ``pallas`` (same kernel family, one direction);
* ``impl="xla"``     — a single ``lax.scan`` per direction (the fused-scan
  analogue at the XLA level; used for the multi-pod dry-run where Pallas
  cannot lower on the CPU backend);
* ``impl="per_step"``— the GSPN-1 emulation (benchmarks only; forward-only).
* ``impl="sp"``      — the spatially-sharded scan (``parallel/gspn_sp.py``,
  DESIGN.md §8): the scan dimension is partitioned over the ``seq`` mesh
  axis, one compact boundary exchange per scan.  Extra kwargs ``mesh`` /
  ``seq_axis`` / ``sp_strategy`` select the mesh axis and collective
  strategy; without a usable mesh it falls back to the single-device path.
* ``impl="auto"``    — pallas/multidir on TPU, xla elsewhere.

Layout: ``x, lam: (G, H, W)``; ``wl, wc, wr: (G_w, H, W)`` with
``G_w ∈ {G, G // channels_per_weight}`` (channel-shared compact mode).
Pair-op operands carry a leading direction axis of size 2.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import gspn_multidir as _mk
from repro.kernels import gspn_scan as _pk
from repro.kernels import ref as _ref
from repro.kernels.spec import ScanSpec


def _base_spec(spec: ScanSpec | None, *, impl, row_tile, interpret,
               carry_dtype, pipeline_depth, boundary) -> ScanSpec:
    """One ScanSpec per public call (DESIGN.md §14): the caller's spec
    verbatim, or one built from the legacy keyword arguments.  The spec
    is the nondiff custom_vjp argument — frozen and hashable by
    construction."""
    if spec is not None:
        return spec
    return ScanSpec(impl=impl, row_tile=row_tile, interpret=interpret,
                    carry_dtype=str(jnp.dtype(carry_dtype)),
                    pipeline_depth=pipeline_depth, boundary=boundary)


def _resolve_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "multidir":
        # The pair kernel family; a single-direction scan through it is
        # just the pallas path.
        return "pallas"
    return impl


def _resolve_pair_impl(impl: str) -> str:
    if impl == "auto":
        return "multidir" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        return "multidir"
    if impl not in ("multidir", "xla"):
        raise ValueError(
            f"impl {impl!r} not supported for the fused pair scan")
    return impl


def _fwd_dispatch(spec: ScanSpec, x, wl, wc, wr, lam):
    impl = _resolve_impl(spec.impl)
    # Traced-dispatch span (DESIGN.md §13): fires once per jit trace.
    with obs.trace("kernel.dispatch", op="gspn_scan", impl=impl,
                   dtype=str(jnp.dtype(x.dtype)), shape=str(x.shape)):
        if impl == "pallas":
            return _pk.gspn_scan_fwd_pallas(x, wl, wc, wr, lam, spec=spec)
        if impl == "xla":
            return _ref_in_carry(spec, x, wl, wc, wr, lam)
        if impl == "per_step":
            return _ref.gspn_scan_per_step(x, wl, wc, wr, lam)
    raise ValueError(f"unknown impl {impl!r}")


def _ref_in_carry(spec: ScanSpec, x, *ops, **kw):
    """The XLA reference scan under the spec's precision policy: the
    recurrence runs in ``carry_dtype`` (f32 under the policy, as in the
    kernels' VMEM carry) on exactly the stream-dtype operands a kernel
    reads, and its output is rounded to the stream dtype as a kernel
    stores it.  ``_rounded`` pins both roundings: XLA's excess-precision
    rewrites would otherwise drop them inside a fusion, and the XLA path
    would then compute something the kernel path never sees.  f32
    streams are untouched."""
    cd = jnp.dtype(spec.carry_dtype)
    out = _ref.gspn_scan_ref(*(_rounded(a).astype(cd) for a in (x,) + ops),
                             **kw)
    return _rounded(out.astype(x.dtype))


def _rounded(a):
    """``a`` with its narrow-float rounding pinned (identity on values)."""
    if a.dtype == jnp.float32:
        return a
    fi = jnp.finfo(a.dtype)
    return jax.lax.reduce_precision(a, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def _bwd_adjoint_xla(dy, wl_b, wc_b, wr_b, reverse: bool = True):
    """Adjoint scan via lax.scan; weights pre-broadcast to full G. f32 out.

    ``reverse=True`` is the adjoint of the top→bottom forward scan (walks
    rows last→first); ``reverse=False`` is the adjoint of the bottom→top
    forward scan (walks rows first→last).
    """
    zeros = jnp.zeros_like(dy[:, 0], dtype=jnp.float32)

    def body(prods, row):
        dy_r, wl_r, wc_r, wr_r = row
        p_l, p_c, p_r = prods
        g_r = (dy_r.astype(jnp.float32)
               + _ref._shift_left(p_l) + p_c + _ref._shift_right(p_r))
        wf = (wl_r.astype(jnp.float32), wc_r.astype(jnp.float32),
              wr_r.astype(jnp.float32))
        return (wf[0] * g_r, wf[1] * g_r, wf[2] * g_r), g_r

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (dy, wl_b, wc_b, wr_b))
    _, gs = jax.lax.scan(body, (zeros, zeros, zeros), xs, reverse=reverse)
    return jnp.moveaxis(gs, 0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gspn_core(spec: ScanSpec, x, wl, wc, wr, lam):
    return _fwd_dispatch(spec, x, wl, wc, wr, lam)


def _gspn_core_fwd(spec, x, wl, wc, wr, lam):
    h = _fwd_dispatch(spec, x, wl, wc, wr, lam)
    return h, (x, wl, wc, wr, lam, h)


def _gspn_core_bwd(spec, res, dy):
    x, wl, wc, wr, lam, h = res
    g_dim = x.shape[0]
    cpw = spec.channels_per_weight
    impl = _resolve_impl(spec.impl)

    with obs.trace("kernel.dispatch", op="gspn_scan_bwd", impl=impl,
                   dtype=str(jnp.dtype(dy.dtype)), shape=str(dy.shape)):
        if impl == "pallas":
            g = _pk.gspn_scan_bwd_pallas(dy, wl, wc, wr,
                                         spec=spec.adjoint())
        else:
            wl_b = _ref._broadcast_w(wl, g_dim)
            wc_b = _ref._broadcast_w(wc, g_dim)
            wr_b = _ref._broadcast_w(wr, g_dim)
            g = _bwd_adjoint_xla(dy, wl_b, wc_b, wr_b)

    g = g.astype(jnp.float32)
    h32 = h.astype(jnp.float32)
    h_prev = jnp.concatenate(
        [jnp.zeros_like(h32[:, :1]), h32[:, :-1]], axis=1)
    dx = (lam.astype(jnp.float32) * g).astype(x.dtype)
    dlam = (x.astype(jnp.float32) * g).astype(lam.dtype)
    dwl = g * _ref._shift_right(h_prev)
    dwc = g * h_prev
    dwr = g * _ref._shift_left(h_prev)
    if cpw > 1:
        gw = g_dim // cpw
        shp = (gw, cpw) + dwl.shape[1:]
        dwl = dwl.reshape(shp).sum(axis=1)
        dwc = dwc.reshape(shp).sum(axis=1)
        dwr = dwr.reshape(shp).sum(axis=1)
    return (dx, dwl.astype(wl.dtype), dwc.astype(wc.dtype),
            dwr.astype(wr.dtype), dlam)


_gspn_core.defvjp(_gspn_core_fwd, _gspn_core_bwd)


def gspn_scan(x, wl, wc, wr, lam, *, spec: ScanSpec | None = None,
              chunk: int | None = None,
              impl: str = "auto", row_tile: int | None = None,
              interpret: bool | None = None, mesh=None,
              seq_axis: str = "seq",
              sp_strategy: str = "auto", carry_dtype="float32",
              sp_boundary_dtype=None, pipeline_depth: int | None = None,
              boundary: str = "one_shot"):
    """GSPN line scan with optional GSPN-local chunking.

    x, lam: (G, H, W); wl/wc/wr: (G_w, H, W), G_w divides G.
    Returns h: (G, H, W) in x.dtype.  Differentiable in all tensor args.
    Configuration travels as ONE ``ScanSpec`` (DESIGN.md §14): pass
    ``spec=`` directly, or let the legacy knob kwargs (``impl`` /
    ``row_tile`` / ``interpret`` / ``carry_dtype`` / ``pipeline_depth``
    / ``boundary``) build one — they are ignored when ``spec`` is given.
    ``mesh``/``seq_axis``/``sp_strategy``/``sp_boundary_dtype`` are sp
    ROUTING arguments (where the scan runs / the wire dtype), not scan
    policy, so they stay outside the spec and only apply to
    ``impl="sp"``.
    """
    spec = _base_spec(spec, impl=impl, row_tile=row_tile,
                      interpret=interpret, carry_dtype=carry_dtype,
                      pipeline_depth=pipeline_depth, boundary=boundary)
    if spec.impl == "sp":
        from repro.parallel.gspn_sp import gspn_scan_sp
        return gspn_scan_sp(x, wl, wc, wr, lam, spec=spec, mesh=mesh,
                            axis_name=seq_axis, strategy=sp_strategy,
                            chunk=chunk, boundary_dtype=sp_boundary_dtype)
    g, h, w = x.shape
    gw = wl.shape[0]
    assert g % gw == 0, (g, gw)
    cpw = g // gw
    # Refine the shape/operand-derived legs the caller cannot know.
    spec = spec.with_(direction="fwd",
                      stream_dtype=str(jnp.dtype(x.dtype)))

    if chunk is not None and chunk != h:
        assert h % chunk == 0, (h, chunk)
        n = h // chunk
        # Differentiable broadcast + fold; core then runs with cpw=1 so the
        # chunk index can be absorbed into the leading grid dimension.
        wl_b = _ref._broadcast_w(wl, g)
        wc_b = _ref._broadcast_w(wc, g)
        wr_b = _ref._broadcast_w(wr, g)

        def fold(a):
            return a.reshape(g * n, chunk, w)

        out = _gspn_core(spec.with_(channels_per_weight=1), fold(x),
                         fold(wl_b), fold(wc_b), fold(wr_b), fold(lam))
        return out.reshape(g, h, w)

    return _gspn_core(spec.with_(channels_per_weight=cpw),
                      x, wl, wc, wr, lam)


# ---------------------------------------------------------------------------
# Fused opposite-direction pair scan (DESIGN.md §2).
#
# Semantics per pair entry (both in the UNFLIPPED layout of x):
#   out[0][i] = wl[0,i]*h[i-1,j-1] + wc[0,i]*h[i-1,j] + wr[0,i]*h[i-1,j+1]
#               + lam[0,i]*x[i]            (top→bottom)
#   out[1][i] = same recurrence with i-1 -> i+1   (bottom→top)
# ---------------------------------------------------------------------------

def _pair_fwd_dispatch(spec: ScanSpec, x, wl2, wc2, wr2, lam2):
    impl = _resolve_pair_impl(spec.impl)
    with obs.trace("kernel.dispatch", op="gspn_scan_pair", impl=impl,
                   dtype=str(jnp.dtype(x.dtype)), shape=str(x.shape)):
        if impl == "multidir":
            return _mk.gspn_scan_bidir_pallas(
                x, {"wl": wl2, "wc": wc2, "wr": wr2}, lam2, spec=spec)
        fwd = _ref_in_carry(spec, x, wl2[0], wc2[0], wr2[0], lam2[0])
        rev = _ref_in_carry(spec, x, wl2[1], wc2[1], wr2[1], lam2[1],
                            reverse=True)
        return jnp.stack([fwd, rev])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gspn_pair_core(spec: ScanSpec, x, wl2, wc2, wr2, lam2):
    return _pair_fwd_dispatch(spec, x, wl2, wc2, wr2, lam2)


def _gspn_pair_fwd(spec, x, wl2, wc2, wr2, lam2):
    h2 = _pair_fwd_dispatch(spec, x, wl2, wc2, wr2, lam2)
    return h2, (x, wl2, wc2, wr2, lam2, h2)


def _gspn_pair_bwd(spec, res, dy2):
    x, wl2, wc2, wr2, lam2, h2 = res
    g_dim = x.shape[0]
    cpw = spec.channels_per_weight
    impl = _resolve_pair_impl(spec.impl)

    with obs.trace("kernel.dispatch", op="gspn_scan_pair_bwd", impl=impl,
                   dtype=str(jnp.dtype(dy2.dtype)), shape=str(dy2.shape)):
        if impl == "multidir":
            g2 = _mk.gspn_scan_bidir_bwd_pallas(dy2, wl2, wc2, wr2,
                                                spec=spec.adjoint())
        else:
            gs = []
            for d, reverse in ((0, True), (1, False)):
                wl_b = _ref._broadcast_w(wl2[d], g_dim)
                wc_b = _ref._broadcast_w(wc2[d], g_dim)
                wr_b = _ref._broadcast_w(wr2[d], g_dim)
                gs.append(_bwd_adjoint_xla(dy2[d], wl_b, wc_b, wr_b,
                                           reverse=reverse))
            g2 = jnp.stack(gs)

    g2 = g2.astype(jnp.float32)
    h32 = h2.astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    # Previous-row state per direction: d=0 reads row i-1, d=1 reads i+1.
    h_prev = jnp.stack([
        jnp.concatenate([jnp.zeros_like(h32[0, :, :1]), h32[0, :, :-1]],
                        axis=1),
        jnp.concatenate([h32[1, :, 1:], jnp.zeros_like(h32[1, :, :1])],
                        axis=1),
    ])
    dx = ((lam2[0].astype(jnp.float32) * g2[0])
          + (lam2[1].astype(jnp.float32) * g2[1])).astype(x.dtype)
    dlam2 = (x32[None] * g2).astype(lam2.dtype)
    dwl2 = g2 * _ref._shift_right(h_prev)
    dwc2 = g2 * h_prev
    dwr2 = g2 * _ref._shift_left(h_prev)
    if cpw > 1:
        gw = g_dim // cpw
        shp = (2, gw, cpw) + dwl2.shape[2:]
        dwl2 = dwl2.reshape(shp).sum(axis=2)
        dwc2 = dwc2.reshape(shp).sum(axis=2)
        dwr2 = dwr2.reshape(shp).sum(axis=2)
    return (dx, dwl2.astype(wl2.dtype), dwc2.astype(wc2.dtype),
            dwr2.astype(wr2.dtype), dlam2)


_gspn_pair_core.defvjp(_gspn_pair_fwd, _gspn_pair_bwd)


def gspn_scan_pair(x, wl2, wc2, wr2, lam2, *, spec: ScanSpec | None = None,
                   chunk: int | None = None,
                   impl: str = "auto", row_tile: int | None = None,
                   interpret: bool | None = None, mesh=None,
                   seq_axis: str = "seq",
                   sp_strategy: str = "auto", carry_dtype="float32",
                   sp_boundary_dtype=None, pipeline_depth: int | None = None,
                   boundary: str = "one_shot"):
    """Fused opposite-direction pair scan with optional GSPN-local chunking.

    x: (G, H, W) — SHARED by both directions; wl2/wc2/wr2: (2, G_w, H, W)
    with G_w dividing G; lam2: (2, G, H, W).  Entry 0 scans top→bottom over
    axis -2, entry 1 bottom→top; all operands and outputs stay in the
    UNFLIPPED layout of x (the reverse traversal is index arithmetic inside
    the kernel, never a flipped copy).  Returns (2, G, H, W) in x.dtype.
    Differentiable in all tensor args.  As for :func:`gspn_scan`,
    configuration travels as ONE ``ScanSpec`` — the knob kwargs are the
    legacy construction path, ignored when ``spec`` is given.
    ``impl="sp"`` shards the pair over the ``seq_axis`` mesh axis with a
    SINGLE shared boundary collective for both directions
    (:func:`repro.parallel.gspn_sp.gspn_scan_sp_pair`, DESIGN.md §8).
    """
    spec = _base_spec(spec, impl=impl, row_tile=row_tile,
                      interpret=interpret, carry_dtype=carry_dtype,
                      pipeline_depth=pipeline_depth, boundary=boundary)
    g, h, w = x.shape
    gw = wl2.shape[1]
    assert g % gw == 0, (g, gw)
    cpw = g // gw
    spec = spec.with_(direction="pair_fwd",
                      stream_dtype=str(jnp.dtype(x.dtype)))

    if spec.impl == "sp":
        from repro.parallel.gspn_sp import gspn_scan_sp_pair
        return gspn_scan_sp_pair(x, wl2, wc2, wr2, lam2, spec=spec,
                                 mesh=mesh, axis_name=seq_axis,
                                 strategy=sp_strategy, chunk=chunk,
                                 boundary_dtype=sp_boundary_dtype)

    if chunk is not None and chunk != h:
        assert h % chunk == 0, (h, chunk)
        n = h // chunk
        wl_b = jnp.stack([_ref._broadcast_w(wl2[d], g) for d in (0, 1)])
        wc_b = jnp.stack([_ref._broadcast_w(wc2[d], g) for d in (0, 1)])
        wr_b = jnp.stack([_ref._broadcast_w(wr2[d], g) for d in (0, 1)])

        def fold(a):           # (G, H, W) -> (G*n, chunk, W)
            return a.reshape(g * n, chunk, w)

        def fold2(a):          # (2, G, H, W) -> (2, G*n, chunk, W)
            return a.reshape(2, g * n, chunk, w)

        out = _gspn_pair_core(spec.with_(channels_per_weight=1), fold(x),
                              fold2(wl_b), fold2(wc_b), fold2(wr_b),
                              fold2(lam2))
        return out.reshape(2, g, h, w)

    return _gspn_pair_core(spec.with_(channels_per_weight=cpw),
                           x, wl2, wc2, wr2, lam2)
