"""Multi-direction dispatch ladder — per-direction vs pair-fused vs
quad-batched (DESIGN.md §2).

The paper's §4.3 point is that directional passes should share one launch,
not pay per-direction dispatch + flipped-copy overhead.  On CPU/XLA we
reproduce the ladder structurally (like fig3) and additionally *prove* the
launch counts of the Pallas path by counting ``pallas_call`` invocations:

  per_direction   four sequential scans over flipped/transposed copies
                  (the GSPN-1 shape of the dispatch; 4 launches)
  pair_fused      opposite pairs fused, reverse traversal by index
                  arithmetic, one transpose at the dispatch boundary
                  (2 launches, no flipped copies)
  quad_batched    all four directions batched into ONE scan call by
                  stacking the oriented operands along G (1 launch;
                  square grids)
"""

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.core import gspn as G
from repro.kernels import gspn_multidir as MK
from repro.kernels import gspn_scan as GS
from repro.kernels.ops import gspn_scan

# Square so the quad-batched rung applies (CPU-scaled).
B, CP, H, W = 2, 4, 192, 192


def _inputs(b, cp, h, w, seed=0):
    g = b * cp
    nd = len(G.DIRECTIONS)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (g, h, w))
    lam = jax.nn.sigmoid(jax.random.normal(ks[1], (nd, g, h, w)))
    logits = jax.random.normal(ks[2], (nd, b, h, w, 3))
    wls, wcs, wrs = [], [], []
    for i, d in enumerate(G.DIRECTIONS):
        wl, wc, wr = G._normalize_taps_oriented(logits[i], d, "softmax")
        wls.append(wl)
        wcs.append(wc)
        wrs.append(wr)
    return x, jnp.stack(wls), jnp.stack(wcs), jnp.stack(wrs), lam


def _per_direction(x, wl, wc, wr, lam):
    return jnp.stack([
        G.directional_scan(x, wl[i], wc[i], wr[i], lam[i], d, impl="xla")
        for i, d in enumerate(G.DIRECTIONS)])


def _pair_fused(x, wl, wc, wr, lam):
    return G.directional_scan(x, wl, wc, wr, lam, G.DIRECTIONS, impl="xla")


def _quad_batched(x, wl, wc, wr, lam):
    """One scan call: directions become batched data parallelism along G
    (needs oriented operand copies — the traffic/launch trade-off the
    fused Pallas quad kernel removes)."""
    g = x.shape[0]
    cat = lambda parts: jnp.concatenate(parts, axis=0)
    xs = cat([G._to_canonical(x, d) for d in G.DIRECTIONS])
    ws = [cat([G._to_canonical(w[i], d) for i, d in enumerate(G.DIRECTIONS)])
          for w in (wl, wc, wr)]
    ls = cat([G._to_canonical(lam[i], d)
              for i, d in enumerate(G.DIRECTIONS)])
    h = gspn_scan(xs, ws[0], ws[1], ws[2], ls, impl="xla")
    return jnp.stack([G._from_canonical(h[i * g:(i + 1) * g], d)
                      for i, d in enumerate(G.DIRECTIONS)])


def _count_pallas_launches(fn):
    """Kernel launches ``fn`` issues, counted at the repo's one launch
    funnel (``gspn_scan.pallas_call``)."""
    n = [0]
    real = GS.pallas_call

    def wrap(*a, **k):
        n[0] += 1
        return real(*a, **k)

    GS.pallas_call = wrap
    try:
        jax.block_until_ready(fn())
    finally:
        GS.pallas_call = real
    return n[0]


def run():
    x, wl, wc, wr, lam = _inputs(B, CP, H, W)

    t0 = time_fn(jax.jit(_per_direction), x, wl, wc, wr, lam)
    emit("multidir/per_direction_ms", t0 * 1e6,
         "launches=4;cum_speedup=1.00")

    t1 = time_fn(jax.jit(_pair_fused), x, wl, wc, wr, lam)
    emit("multidir/pair_fused_ms", t1 * 1e6,
         f"launches=2;cum_speedup={t0/t1:.2f}")

    t2 = time_fn(jax.jit(_quad_batched), x, wl, wc, wr, lam)
    emit("multidir/quad_batched_ms", t2 * 1e6,
         f"launches=1;cum_speedup={t0/t2:.2f}")

    # Launch-count proof on the actual Pallas path (tiny shape, interpret).
    xt, wlt, wct, wrt, lamt = _inputs(1, 2, 8, 8, seed=1)
    n_per = _count_pallas_launches(lambda: jnp.stack([
        G.directional_scan(xt, wlt[i], wct[i], wrt[i], lamt[i], d,
                           impl="multidir")
        for i, d in enumerate(G.DIRECTIONS)]))
    n_pair = _count_pallas_launches(lambda: G.directional_scan(
        xt, wlt, wct, wrt, lamt, G.DIRECTIONS, impl="multidir"))
    T = lambda a: jnp.swapaxes(a, -1, -2)
    taps4 = {k: jnp.stack([v[0], v[1], T(v[2]), T(v[3])])
             for k, v in (("wl", wlt), ("wc", wct), ("wr", wrt))}
    lam4 = jnp.stack([lamt[0], lamt[1], T(lamt[2]), T(lamt[3])])
    n_quad = _count_pallas_launches(lambda: MK.gspn_scan_quad_pallas(
        xt, taps4, lam4, channels_per_weight=2, row_tile=4))
    emit("multidir/pallas_launches", 0.0,
         f"per_direction={n_per};pair_fused={n_pair};quad={n_quad}")
    assert n_pair <= 2 and n_quad == 1, (n_per, n_pair, n_quad)
    return {"pair_speedup": t0 / t1, "launches": (n_per, n_pair, n_quad)}


if __name__ == "__main__":
    run()
