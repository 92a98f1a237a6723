"""Serving launcher: load (or init) a model and serve a synthetic request
stream through the continuous-batching engine (DESIGN.md §9) — or, with
``--replicas N``, through the data-parallel serving tier (DESIGN.md §15):
N engine replicas behind the SLO-aware router.

Engine knobs surfaced here: ``--max-batch`` (decode slots per replica),
``--prefill-chunk`` (0 = one-shot prefill; otherwise prompts are consumed
in chunks interleaved with decode), ``--scheduler fcfs|sjf``, ``--impl``
(GSPN kernel selection threaded into the model config),
``--seq-parallel`` (serve through a `seq`-axis mesh so the GSPN scans
shard across devices, DESIGN.md §8), ``--state-dtype bf16`` (narrow the
pooled propagation state at rest — half the pool bytes, ~2× decode batch
at fixed memory) and ``--precision bf16`` (run the model itself under the
mixed-precision policy, DESIGN.md §10).

Tier knobs (shared definitions in ``launch/args.py``): ``--replicas``,
``--router least_loaded|ttft``, ``--prefix-cache N`` (shared prefix/state
cache entries; prompts sharing a chunk-aligned prefix resume prefill from
cached boundary state), ``--slo-ttft`` (seconds; predicted-miss
admissions are counted, DESIGN.md §15).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --reduced --requests 8 --prefill-chunk 128 --scheduler sjf \
        --replicas 2 --router ttft --prefix-cache 8
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro import obs
from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import get_arch, resolve_dtype, with_precision
from repro.launch import args as largs
from repro.launch import compile_cache
from repro.models.lm import Ctx, init_lm
from repro.serve.cache import PrefixStateCache
from repro.serve.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", "--batch", type=int, default=4,
                    dest="max_batch")
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill size in tokens (0 = one-shot)")
    ap.add_argument("--scheduler", default="fcfs", choices=["fcfs", "sjf"])
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seq-parallel", type=int, default=1,
                    help="carve a seq mesh axis of this size and serve "
                         "the sharded model (impl=sp, DESIGN.md §8)")
    ap.add_argument("--ckpt-dir", default="")
    largs.add_impl_arg(ap)
    largs.add_precision_args(ap, state_dtype=True)
    largs.add_tuning_args(ap)
    largs.add_router_args(ap)
    largs.add_observability_args(ap)
    args = ap.parse_args()

    compile_cache.enable()
    largs.setup_observability(args)
    largs.load_tune_cache(args, "serve")

    entry = get_arch(args.arch)
    cfg = entry.reduced() if args.reduced else entry.full()
    if args.precision:
        cfg = with_precision(cfg, args.precision)
    if args.impl:
        cfg = dataclasses.replace(cfg, gspn_impl=args.impl)

    ctx = None
    if args.seq_parallel > 1:
        from repro.launch.mesh import dp_axes_for, make_sp_mesh
        mesh = make_sp_mesh(args.seq_parallel)
        ctx = Ctx(mesh=mesh, dp_axes=dp_axes_for(mesh))
        if not args.impl:
            # the mesh is only consulted by impl="sp"; without this the
            # seq axis would be carved and then silently unused
            cfg = dataclasses.replace(cfg, gspn_impl="sp")
        print(f"[serve] mesh axes {dict(zip(mesh.axis_names, mesh.shape))} "
              f"(gspn impl={cfg.gspn_impl})")

    params = init_lm(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        restored, step = mgr.restore(target={"params": params})
        params = restored["params"]
        print(f"[serve] restored checkpoint step {step}")

    prefix_cache = (PrefixStateCache(capacity=args.prefix_cache)
                    if args.prefix_cache > 0 else None)

    def make_engine(seed=0):
        return ServeEngine(
            params, cfg, batch_size=args.max_batch, max_len=args.max_len,
            temperature=args.temperature, prefill_chunk=args.prefill_chunk,
            scheduler=args.scheduler, ctx=ctx, seed=seed,
            prefix_cache=prefix_cache,
            state_dtype=(resolve_dtype(args.state_dtype)
                         if args.state_dtype else None))

    if args.replicas > 1:
        from repro.serve.router import Router
        engines = [make_engine(seed=i) for i in range(args.replicas)]
        tier = Router(engines, policy=args.router, slo_ttft=args.slo_ttft)
        pool = engines[0].pool
        chunk = engines[0].prefill_chunk
        print(f"[serve] router: {args.replicas} replicas, "
              f"policy={args.router}, slo_ttft={args.slo_ttft * 1e3:.0f} ms"
              + (f", prefix cache {args.prefix_cache} entries"
                 if prefix_cache else ""))
    else:
        tier = make_engine()
        pool, chunk = tier.pool, tier.prefill_chunk
    if args.state_dtype:
        print(f"[serve] state pool dtype {args.state_dtype}: "
              f"{args.replicas * pool.nbytes/2**20:.1f} MiB pooled state")

    rng = np.random.default_rng(0)
    # Discrete prompt lengths (each distinct length is a separate jit
    # trace of the prefill); when chunking is on, the long length must
    # actually exceed the (alignment-snapped) chunk so the chunked path
    # runs at this entry point's workload sizes.
    long_len = min(args.max_len - args.max_new, 3 * chunk) if chunk else 24
    handles = []
    for i in range(args.requests):
        plen = long_len if (chunk and i % 2) else 12
        handles.append(tier.submit(Request(
            uid=i, prompt=rng.integers(0, cfg.vocab, max(plen, 4)),
            max_new_tokens=args.max_new)))
    t0 = obs.monotonic()
    tier.run()
    dt = obs.monotonic() - t0
    largs.finish_observability(args, "serve")
    results = [h.result() for h in handles]
    if not results:
        print(f"[serve] {args.arch}: 0 requests")
        return
    total = sum(len(r.tokens) for r in results)
    ttfts = sorted(r.ttft for r in results)
    cached = sum(r.cached_tokens for r in results)
    print(f"[serve] {args.arch}: {len(results)} requests, {total} tokens, "
          f"{total/dt:.1f} tok/s"
          + (f", {cached} prompt tokens prefix-cached" if cached else ""))
    if args.replicas > 1:
        placed = [h.replica for h in handles]
        snap = obs.snapshot()
        risk = snap.get("counters", {}).get("router_slo_at_risk_total", 0)
        print(f"[serve] placement: "
              f"{[placed.count(r) for r in range(args.replicas)]} "
              f"requests/replica; {risk} admissions predicted past SLO")
        print(f"[serve] ttft p50 {ttfts[len(ttfts)//2]*1e3:.1f} ms, "
              f"max {ttfts[-1]*1e3:.1f} ms")
    else:
        m = tier.metrics
        print(f"[serve] ttft p50 {ttfts[len(ttfts)//2]*1e3:.1f} ms, "
              f"max {ttfts[-1]*1e3:.1f} ms; queue depth "
              f"mean {m['queue_depth_mean']:.1f} / "
              f"max {m['queue_depth_max']}; "
              f"{m['prefill_chunks']} prefill chunks / "
              f"{m['decode_steps']} decode steps over {m['ticks']} ticks")


if __name__ == "__main__":
    main()
