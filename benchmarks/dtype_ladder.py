"""Mixed-precision ladder — f32 vs bf16 across impl × resolution
(DESIGN.md §10), plus the serve-state byte ledger.

Three things are measured per (dtype, impl, resolution) rung:

* wall time of the fused forward scan (``us_per_call``) and, per dtype,
  of one fwd+bwd step through the custom-vjp entry point — on TPU the
  bf16 rungs stream half the HBM bytes and the tuner doubles the row
  tile (on CPU/interpret the timing is structural, like fig3); each
  pallas rung also reports the resolved ``(row_tile, pipeline_depth)``
  plan, and the gate's ordering check (``gate.py``) enforces that bf16
  pallas fwd strictly beats f32 at every resolution (DESIGN.md §12);
* the bf16 rel-L2 error against the f32 oracle for the same inputs —
  the number the §10 error-budget table pins (≤ 1e-2);
* the analytic streamed bytes (benchmarks.common.scan_bytes) so the
  traffic halving is visible even where timings are noisy.

The final rung builds a small served model's StateCachePool at f32 and
bf16 and reports the byte ratio — the ``--state-dtype bf16`` payoff: the
pool is what bounds decode batch at fixed memory, and the ratio is
asserted ≥ 1.9× (the integer length/pos leaves keep it just under 2×).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import benchmarks.common as common
from benchmarks.common import emit, make_gspn_inputs, scan_bytes, time_fn
from repro.kernels import ScanSpec, autotune
from repro.kernels.ops import gspn_scan
from repro.kernels.tuning import pick_row_tile_for_policy
from repro.models.lm import LMConfig
from repro.serve.cache import StateCachePool

RESOLUTIONS = [(128, 128), (256, 256)]
IMPLS = ["xla", "pallas"]
DTYPES = [("f32", jnp.float32), ("bf16", jnp.bfloat16)]
B, CP = 2, 4

# Byte-ratio floor the serve-state rung must clear (ISSUE 4 acceptance):
# float leaves halve exactly; int32 lengths/positions keep it under 2.
MIN_STATE_BYTE_RATIO = 1.9


def _serve_cfg():
    return LMConfig(
        name="dtype-ladder", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
        prelude=(("gspn", 1),), unit=(("attn", 1),), n_units=1,
        gspn_proxy_dim=4, gspn_row_width=16, remat="none")


def _step(x, wl, wc, wr, lam, impl):
    def loss(x, wl, wc, wr, lam):
        return jnp.sum(
            gspn_scan(x, wl, wc, wr, lam, impl=impl).astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 4))(x, wl, wc, wr, lam)


def run():
    resolutions = RESOLUTIONS[:1] if common.SMOKE else RESOLUTIONS
    for h, w in resolutions:
        inputs32 = make_gspn_inputs(B, CP, h, w)
        by_dtype = {dname: tuple(a.astype(dtype) for a in inputs32)
                    for dname, dtype in DTYPES}
        fwds = {impl: jax.jit(lambda *a, impl=impl: gspn_scan(*a, impl=impl))
                for impl in IMPLS}
        # The pallas fwd rungs feed the gate's STRICT bf16<f32 ordering
        # check: time them interleaved (a load burst lands on both
        # dtypes) and keep a median-of-5 even under --smoke.
        gated = dict(zip(by_dtype, common.time_interleaved(
            [(fwds["pallas"], by_dtype[d]) for d in by_dtype],
            iters=5, min_iters=5)))
        ref = None
        for dname, dtype in DTYPES:
            inputs = by_dtype[dname]
            for impl in IMPLS:
                fwd = fwds[impl]
                if impl == "pallas":
                    t_f, common.LAST_STATS = gated[dname]
                else:
                    t_f = time_fn(fwd, *inputs, iters=5)
                out = np.asarray(fwd(*inputs), np.float32)
                if dname == "f32" and impl == "xla":
                    ref = out
                err = (np.linalg.norm(out - ref)
                       / max(np.linalg.norm(ref), 1e-30))
                nbytes = jnp.dtype(dtype).itemsize
                # Byte widths follow the named precision policy (DESIGN.md
                # §10) instead of a hand-passed constant, and the emitted
                # tile is what the launch actually used: the tuner's
                # cached choice with the policy heuristic as fallback
                # (DESIGN.md §11).  The spec legs are derived from the
                # operands (not hand-written) so they track the launch's
                # own resolution inside gspn_scan_fwd_pallas (§14).
                x_in, wl_in = inputs[0], inputs[1]
                cpw = x_in.shape[0] // wl_in.shape[0]
                plan = autotune.plan_for_spec(
                    ScanSpec(direction="fwd", impl="pallas",
                             channels_per_weight=cpw,
                             stream_dtype=str(jnp.dtype(dtype))),
                    h, w, c=x_in.shape[0])
                heur = pick_row_tile_for_policy(
                    h, w, dname, cap=autotune.DEFAULT_CAP,
                    pipeline_depth=plan.pipeline_depth).row_tile
                mb = scan_bytes(B, CP, h, w, dtype_bytes=nbytes) / 2 ** 20
                emit(f"dtype/{dname}/{impl}/{h}x{w}/fwd", t_f * 1e6,
                     f"rel_err={err:.2e};row_tile={plan.row_tile};"
                     f"pipeline_depth={plan.pipeline_depth};heur={heur};"
                     f"stream_mb={mb:.1f}")
            step = jax.jit(lambda *a: _step(*a, impl="xla"))
            t_s = time_fn(step, *inputs)
            emit(f"dtype/{dname}/xla/{h}x{w}/step", t_s * 1e6, "")

    # Serve-state byte ledger: the ≥1.9× reduction the acceptance pins.
    # The f32 rung pins an explicitly-f32 pool (the full-f32 policy; the
    # repo default already kept KV pages in cfg.compute_dtype, but GSPN /
    # SSM propagation state was f32) against --state-dtype bf16.
    cfg = _serve_cfg()
    pool32 = StateCachePool(cfg, n_slots=4, max_len=256,
                            state_dtype=jnp.float32)
    pool16 = StateCachePool(cfg, n_slots=4, max_len=256,
                            state_dtype=jnp.bfloat16)
    ratio = pool32.nbytes / pool16.nbytes
    emit("dtype/serve_state_bytes", 0.0,
         f"f32={pool32.nbytes};bf16={pool16.nbytes};ratio={ratio:.3f}")
    assert ratio >= MIN_STATE_BYTE_RATIO, (
        f"serve-state byte reduction {ratio:.3f}x < {MIN_STATE_BYTE_RATIO}x")
    return {"state_byte_ratio": ratio}


if __name__ == "__main__":
    run()
