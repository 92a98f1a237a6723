"""Pallas TPU kernels for the GSPN-2 fused line scan.

TPU adaptation of the paper's single-CUDA-kernel design (DESIGN.md §2):

* the whole scan runs inside **one** ``pl.pallas_call`` — the grid walks
  ``(D, G, H_tiles)`` sequentially and the row loop runs *inside* the
  kernel, eliminating the per-step dispatches of GSPN-1;
* the previous row's hidden state is staged in a **VMEM scratch carry**
  that persists across sequential grid steps — the TPU analogue of the
  paper's shared-memory staging of ``h[i-1]`` (it never round-trips to HBM);
* W is the innermost (lane) dimension so the tridiagonal matvec becomes
  three shifted vector FMAs on fully-coalesced tiles — the analogue of the
  paper's coalesced-access layout;
* channel-shared propagation weights are expressed through the BlockSpec
  ``index_map`` (``g // channels_per_weight``) so the compact-channel mode
  reads each weight tile once per channel group instead of materialising a
  broadcast — the paper's compact channel propagation;
* the channel-slice grid axis plays the role of the paper's 2D thread
  blocks (spatial × cSlice).

ONE kernel body (:func:`_scan_kernel`) serves every launch: the forward
and adjoint recurrences, one direction or a fused opposite pair / quad
(``gspn_multidir``), at either pipeline depth (DESIGN.md §12).  A
direction that walks rows last→first does so by index arithmetic — its
tiles in reverse through the ``index_map``, its rows in reverse inside
the kernel — so no flipped copy of any operand exists.

Array layout: ``x, lam, out: (G, H, W)``; ``wl, wc, wr: (G_w, H, W)`` with
``G = G_w * channels_per_weight``.  All kernels compute in f32 and cast the
output back to the input dtype; the VMEM carry row is kept in
``carry_dtype`` (f32 under the default mixed-precision policy, DESIGN.md
§10) while the streamed tiles take whatever dtype the operands carry, so
bf16 operands halve the streamed working set.

Interpret mode is used only where the backend cannot compile a kernel:
unless a ``ScanSpec`` sets ``interpret`` explicitly, every launch picks
the Mosaic kernel when lowered for a TPU and the interpreter otherwise
(:func:`pallas_call`).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels import autotune, tuning
from repro.kernels.spec import ScanSpec

DEFAULT_ROW_TILE = 256


def pick_row_tile(h: int, cap: int = DEFAULT_ROW_TILE, *, w: int = 128,
                  dtype_bytes: int = 4, n_streams: int = 6,
                  carry_dtype_bytes: int = 4,
                  pipeline_depth: int = 1) -> int:
    """Heuristic row-tile choice (the tuner's fallback tier).

    Thin wrapper (old signature preserved) over the single VMEM-aware
    implementation in :func:`repro.kernels.tuning.pick_row_tile`.
    ``dtype_bytes`` is the STREAMED dtype; ``carry_dtype_bytes`` the VMEM
    carry's.  Launch sites no longer call this directly — they go through
    ``autotune.plan_for_spec``, which prefers a measured cache entry and
    falls back to this accounting (DESIGN.md §11/§12).
    """
    return tuning.pick_row_tile(h, w, dtype_bytes, cap=cap,
                                n_streams=n_streams,
                                carry_dtype_bytes=carry_dtype_bytes,
                                pipeline_depth=pipeline_depth).row_tile


def pallas_call(kernel, *, interpret: bool | None, **kwargs):
    """``pl.pallas_call`` with the interpret decision made per platform.

    An explicit ``interpret`` (a ``ScanSpec`` override — the compile
    tests pass ``False``) wins.  ``None`` defers the choice to lowering
    (``lax.platform_dependent``): the Mosaic kernel for a TPU — on the
    chip, or ahead of time for a described one — and the interpreter for
    every other backend.  So no TPU lowering ever reaches the
    interpreter, and nothing on the CPU needs a flag."""
    if interpret is not None:
        return pl.pallas_call(kernel, interpret=interpret, **kwargs)
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)
    return lambda *args: jax.lax.platform_dependent(
        *args, tpu=compiled, default=interpreted)


def _masked_shifts(shape):
    """Edge-masked lane shifts ``sr: v[j] -> v[j-1]`` and ``sl: v[j] ->
    v[j+1]`` (the vacated edge lane becomes 0), with the iota/compare
    hoisted OUT of the sequential loop: the masks are built once per grid
    step, so each row step pays one roll + one select per shift.  A
    one-lane row has no neighbours (and Mosaic no zero-width slice)."""
    if shape[-1] == 1:
        return jnp.zeros_like, jnp.zeros_like
    idx = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    first, last = idx == 0, idx == shape[-1] - 1

    def sr(v):
        return jnp.where(first, 0.0, jnp.roll(v, 1, axis=-1))

    def sl(v):
        return jnp.where(last, 0.0, jnp.roll(v, -1, axis=-1))

    return sr, sl


# ---------------------------------------------------------------------------
# The two recurrences, written once for both depths.  A step maps the f32
# carry tuple and one f32 row of every input stream to (new carry, output
# row).  ``lam*x`` stays inside the step on purpose: hoisting it to a bulk
# multiply changes which mul/add pairs the CPU backend contracts into
# FMAs, breaking the bitwise depth-1 ≡ depth-2 agreement in f32 streams.
# ---------------------------------------------------------------------------

def _fwd_step(sr, sl, carry, row):
    """h[i] = wl·h[i-1, j-1] + wc·h[i-1, j] + wr·h[i-1, j+1] + λ·x."""
    (h_prev,) = carry
    x_r, wl_r, wc_r, wr_r, lam_r = row
    h_new = (
        wl_r * sr(h_prev)
        + wc_r * h_prev
        + wr_r * sl(h_prev)
        + lam_r * x_r
    )
    return (h_new,), h_new


def _bwd_step(sr, sl, carry, row):
    """Adjoint: the carry holds the three tap·adjoint products of the
    previously processed (next-in-scan-order) row:
        g[i] = dy[i] + shift_left(wl[i+1]·g[i+1]) + wc[i+1]·g[i+1]
                     + shift_right(wr[i+1]·g[i+1])"""
    prod_l, prod_c, prod_r = carry
    dy_r, wl_r, wc_r, wr_r = row
    g_row = dy_r + sl(prod_l) + prod_c + sr(prod_r)
    return (wl_r * g_row, wc_r * g_row, wr_r * g_row), g_row


@dataclasses.dataclass(frozen=True)
class _KernelCfg:
    adjoint: bool            # _bwd_step (4 inputs, 3 carry rows)
    depth: int               # 1: one plane per grid step; 2: all planes
    row_tile: int
    group: int               # depth 1: rows widened per bulk load
    chunk_tiles: int         # carry resets every chunk_tiles grid steps
    n_dirs: int
    reversed_dirs: tuple     # directions that walk rows last→first
    bcast: tuple             # depth 2: plane broadcast factor per input


def _dir_reversed(reversed_dirs, n_dirs: int, d):
    """Whether grid direction ``d`` walks last→first — a static bool when
    every direction agrees."""
    if not reversed_dirs:
        return False
    if len(reversed_dirs) == n_dirs:
        return True
    return functools.reduce(jnp.logical_or, [d == k for k in reversed_dirs])


def _flip(i, n, reverse):
    """Index ``i`` of ``n``, counted from the end when ``reverse``."""
    return (n - 1 - i) if reverse is True else \
        i if reverse is False else jnp.where(reverse, n - 1 - i, i)


def _walk_rows(cfg, reverse, step, ins, out, stages, init):
    """Depth-1 row recurrence over one plane's ``(1, T, W)`` tile.

    f32 streams are read and written row by row in place.  A narrow
    stream (bf16) is widened ``group`` rows at a time into an f32 stage
    by one aligned bulk load — a single-row load or store of a packed
    tile has no Mosaic lowering — and a narrow output is gathered in an
    f32 stage and written back by one bulk downcast per group."""
    stages = list(stages)
    in_st = [stages.pop(0) if r.dtype != jnp.float32 else None for r in ins]
    out_st = stages.pop(0) if out.dtype != jnp.float32 else None
    group = cfg.group
    n_groups = cfg.row_tile // group

    def group_body(gi, carry):
        base = _flip(gi, n_groups, reverse) * group
        if n_groups > 1:
            base = pl.multiple_of(base, group)
        for ref, st in zip(ins, in_st):
            if st is not None:
                st[...] = ref[0, pl.ds(base, group), :].astype(jnp.float32)

        def row_body(ki, c):
            k = _flip(ki, group, reverse)
            row = [st[pl.ds(k, 1), :] if st is not None
                   else ref[0, pl.ds(base + k, 1), :]
                   for ref, st in zip(ins, in_st)]
            c, y = step(c, row)
            if out_st is not None:
                out_st[pl.ds(k, 1), :] = y
            else:
                out[0, pl.ds(base + k, 1), :] = y
            return c

        carry = jax.lax.fori_loop(0, group, row_body, carry)
        if out_st is not None:
            out[0, pl.ds(base, group), :] = out_st[...].astype(out.dtype)
        return carry

    if n_groups == 1:
        return group_body(0, init)
    return jax.lax.fori_loop(0, n_groups, group_body, init)


def _walk_staged(cfg, reverse, step, ins, out, stages, init):
    """Depth-2 row recurrence over ALL planes of a ``(P, T, W)`` tile.

    Each input block is widened to f32 once, its channel-shared weight
    planes broadcast, and stored transposed as a ``(T, G, W)`` stage: row
    ``r`` of every plane is then one ``(G, W)`` slab at an untiled
    leading index, so the G planes share sublanes and the row loop needs
    no sublane-offset access at all.  The f32 output stage is written
    back by one transpose and one bulk downcast per tile."""
    *in_st, out_st = stages
    for ref, st, rep in zip(ins, in_st, cfg.bcast):
        v = ref[...].astype(jnp.float32)
        if rep > 1:
            p = v.shape[0]
            v = jnp.broadcast_to(v[:, None], (p, rep) + v.shape[1:])
            v = v.reshape((p * rep,) + v.shape[2:])
        st[...] = jnp.swapaxes(v, 0, 1)

    def body(i, c):
        r = _flip(i, cfg.row_tile, reverse)
        c, y = step(c, [st[r] for st in in_st])
        out_st[r] = y
        return c

    carry = jax.lax.fori_loop(0, cfg.row_tile, body, init)
    out[...] = jnp.swapaxes(out_st[...], 0, 1).astype(out.dtype)
    return carry


def _scan_kernel(cfg: _KernelCfg, *refs):
    n_in = 4 if cfg.adjoint else 5
    ins, out, carry_ref = refs[:n_in], refs[n_in], refs[n_in + 1]
    stages = refs[n_in + 2:]
    tile_axis = 2 if cfg.depth == 1 else 1

    @pl.when(pl.program_id(tile_axis) % cfg.chunk_tiles == 0)
    def _reset():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    sr, sl = _masked_shifts(carry_ref.shape[1:])
    step = functools.partial(_bwd_step if cfg.adjoint else _fwd_step, sr, sl)
    reverse = _dir_reversed(cfg.reversed_dirs, cfg.n_dirs, pl.program_id(0))
    # The row recurrence runs in f32 regardless of the streamed dtype; the
    # cross-tile carry is stored in the scratch's dtype (carry_dtype).
    init = tuple(carry_ref[i].astype(jnp.float32)
                 for i in range(carry_ref.shape[0]))
    walk = _walk_rows if cfg.depth == 1 else _walk_staged
    carry = walk(cfg, reverse, step, ins, out, stages, init)
    for i, c in enumerate(carry):
        carry_ref[i] = c.astype(carry_ref.dtype)


def launch_scan(spec: ScanSpec, plan, operands, *, adjoint: bool,
                n_dirs: int = 1, reversed_dirs: tuple = (),
                chunk: int | None = None, out_dtype, name: str):
    """Build and run ONE fused-scan ``pallas_call``.

    ``operands`` are the kernel inputs in step order — fwd ``(x, wl, wc,
    wr, lam)``, adjoint ``(dy, wl, wc, wr)`` — each ``(array, planes)``
    with ``array: (k·planes, H, W)`` holding k stacked direction slices
    (k = 1 for an input shared by every direction, e.g. the pair's x).
    Data streams have G planes, channel-shared weights G_w.  Returns
    ``(n_dirs·G, H, W)`` in ``out_dtype``; direction ``d`` occupies rows
    ``d·G … (d+1)·G``.  ``reversed_dirs`` walk their rows last→first;
    ``chunk`` resets the carry every ``chunk`` rows (GSPN-local
    segments)."""
    _, h, w = operands[0][0].shape
    g = operands[0][1]
    t, depth = plan.row_tile, plan.pipeline_depth
    assert depth in (1, 2), depth
    chunk = h if chunk is None else chunk
    assert h % chunk == 0 and chunk % t == 0, (h, chunk, t)
    n_tiles = h // t
    rev = tuple(sorted(set(reversed_dirs)))

    def tile_of(d, ti):
        return _flip(ti, n_tiles, _dir_reversed(rev, n_dirs, d))

    def dir_of(arr, planes):
        per = n_dirs // (arr.shape[0] // planes)
        return lambda d: d // per

    narrow = [a.dtype for a, _ in operands if a.dtype != jnp.float32]
    if jnp.dtype(out_dtype) != jnp.float32:
        narrow.append(out_dtype)
    itemsize = min([jnp.dtype(dt).itemsize for dt in narrow] or [4])
    carry_rows = 3 if adjoint else 1
    carry_dtype = jnp.float32 if adjoint else jnp.dtype(spec.carry_dtype)

    if depth == 1:
        group = tuning.stage_rows(t, itemsize, 1) or t
        in_specs = []
        for arr, planes in operands:
            rep, dmap = g // planes, dir_of(arr, planes)
            in_specs.append(pl.BlockSpec(
                (1, t, w),
                lambda d, gi, ti, rep=rep, dmap=dmap, planes=planes:
                    (dmap(d) * planes + gi // rep, tile_of(d, ti), 0)))
        out_spec = pl.BlockSpec(
            (1, t, w), lambda d, gi, ti: (d * g + gi, tile_of(d, ti), 0))
        grid = (n_dirs, g, n_tiles)
        stages = [pltpu.VMEM((group, w), jnp.float32) for _ in narrow]
        carry = pltpu.VMEM((carry_rows, 1, w), carry_dtype)
        bcast = ()
    else:
        group = t
        in_specs = [pl.BlockSpec(
            (planes, t, w),
            lambda d, ti, dmap=dir_of(arr, planes): (dmap(d), tile_of(d, ti),
                                                     0))
            for arr, planes in operands]
        out_spec = pl.BlockSpec((g, t, w),
                                lambda d, ti: (d, tile_of(d, ti), 0))
        grid = (n_dirs, n_tiles)
        stages = [pltpu.VMEM((t, g, w), jnp.float32)
                  for _ in range(len(operands) + 1)]
        carry = pltpu.VMEM((carry_rows, g, w), carry_dtype)
        bcast = tuple(g // planes for _, planes in operands)

    cfg = _KernelCfg(adjoint=adjoint, depth=depth, row_tile=t, group=group,
                     chunk_tiles=chunk // t, n_dirs=n_dirs,
                     reversed_dirs=rev, bcast=bcast)
    call = pallas_call(
        functools.partial(_scan_kernel, cfg),
        grid=grid, in_specs=in_specs, out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n_dirs * g, h, w), out_dtype),
        scratch_shapes=[carry] + stages,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=tuning.VMEM_BYTES),
        interpret=spec.interpret)
    # Traced-launch span (DESIGN.md §13): fires once per jit trace of this
    # launch site, annotated with the tuner-resolved plan.
    with obs.trace("kernel.launch", kernel=name, row_tile=t,
                   pipeline_depth=depth,
                   dtype=str(jnp.dtype(operands[0][0].dtype)),
                   g=g, h=h, w=w):
        return call(*(a for a, _ in operands))


# ---------------------------------------------------------------------------
# Single-direction entry points.
# ---------------------------------------------------------------------------

def gspn_scan_fwd_pallas(x, wl, wc, wr, lam, *,
                         spec: ScanSpec | None = None,
                         channels_per_weight: int = 1,
                         chunk: int | None = None, row_tile: int | None = None,
                         interpret: bool | None = None,
                         carry_dtype=jnp.float32,
                         pipeline_depth: int | None = None):
    """Fused forward line scan.  Returns h: (G, H, W) in x.dtype.

    Configuration travels as ONE ``ScanSpec`` (DESIGN.md §14); the loose
    keyword arguments survive as a legacy construction path used only
    when ``spec`` is None.  Streamed tiles take the operands' dtype; the
    VMEM carry row persists in ``spec.carry_dtype`` (f32 by default —
    the mixed-precision policy's accumulator discipline, DESIGN.md §10).
    ``spec.pipeline_depth`` selects the kernel structure (DESIGN.md §12):
    1 walks planes × tiles row by row; 2 blocks all planes into each grid
    step and stages the streams in f32 ``(T, G, W)`` layout.  ``None``
    resolves both the tile and the depth through the autotuner (measured
    cache entry keyed on the spec's canonical serialization, heuristic
    fallback).
    """
    g, h, w = x.shape
    if spec is None:
        spec = ScanSpec(channels_per_weight=channels_per_weight,
                        carry_dtype=str(jnp.dtype(carry_dtype)),
                        row_tile=row_tile, pipeline_depth=pipeline_depth,
                        interpret=interpret)
    # Normalise the identity legs this kernel owns: it IS the pallas fwd
    # entry, and it streams whatever dtype the operands carry.
    spec = spec.with_(direction="fwd", impl="pallas",
                      stream_dtype=str(jnp.dtype(x.dtype)))
    gw = g // spec.channels_per_weight
    assert wl.shape[0] == gw, (wl.shape, g, spec.channels_per_weight)
    chunk = h if chunk is None else chunk
    plan = autotune.plan_for_spec(spec, min(h, chunk), w, c=g)
    return launch_scan(spec, plan,
                       [(x, g), (wl, gw), (wc, gw), (wr, gw), (lam, g)],
                       adjoint=False, chunk=chunk, out_dtype=x.dtype,
                       name="gspn_scan_fwd")


def gspn_scan_bwd_pallas(dy, wl, wc, wr, *, spec: ScanSpec | None = None,
                         channels_per_weight: int = 1,
                         chunk: int | None = None, row_tile: int | None = None,
                         interpret: bool | None = None,
                         pipeline_depth: int | None = None):
    """Adjoint scan: walks rows last→first (reverse tile order through
    the index map, reverse row order in the kernel — no flipped copies).
    Returns g = dL/dh pre-output-layer: (G, H, W) f32."""
    g_dim, h, w = dy.shape
    if spec is None:
        spec = ScanSpec(channels_per_weight=channels_per_weight,
                        row_tile=row_tile, pipeline_depth=pipeline_depth,
                        interpret=interpret)
    # The streamed operands are dy + the three taps (their real dtype);
    # the adjoint carry is three f32 tap·adjoint rows regardless of the
    # policy (the "bwd" direction leg encodes both the 5-stream count and
    # the 3-row carry).
    spec = spec.with_(direction="bwd", impl="pallas",
                      stream_dtype=str(jnp.dtype(dy.dtype)),
                      carry_dtype="float32")
    gw = g_dim // spec.channels_per_weight
    chunk = h if chunk is None else chunk
    plan = autotune.plan_for_spec(spec, min(h, chunk), w, c=g_dim)
    return launch_scan(spec, plan,
                       [(dy, g_dim), (wl, gw), (wc, gw), (wr, gw)],
                       adjoint=True, reversed_dirs=(0,), chunk=chunk,
                       out_dtype=jnp.float32, name="gspn_scan_bwd")
