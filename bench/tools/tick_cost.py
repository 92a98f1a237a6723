"""What the program's own tracing costs the serve engine when it is on and
no profiler runs.

    python3 bench/tools/tick_cost.py --seed 7 --seconds 20 --passes 4

Builds the serving cell's engine once, as its job does, and offers the
same traffic (one seed) in passes that alternate ``repro.obs`` tracing
off and on, each from a reset engine.  Prints one JSON line per pass: the
ticks that started in the window, their mean host milliseconds, and the
end-to-end metrics the job computes.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="qwen2gspn.serve_chat")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--passes", type=int, default=4)
    args = ap.parse_args(argv)

    from benchlib import registry, traffic
    from benchlib.harness import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("tick_cost: no TPU", file=sys.stderr)
        return 3
    from repro import obs
    cell = registry.find_cell(args.workload)
    serve = registry.job(cell.traffic["job"])
    cfg, mix = cell.config, cell.traffic
    pcfg = serve.program_config(cfg)
    params = serve.make_weights(pcfg, args.seed)
    reqs = traffic.serve_schedule(mix, args.seconds, args.seed, cfg["vocab"])
    now = {}

    def stream(uid, tok):
        if uid >= 0:
            now["served"].times.setdefault(uid, []).append(
                time.perf_counter())

    engine = serve.make_engine(params, pcfg, mix, args.seed, stream=stream)
    serve.warm(engine, traffic.prompt_lengths(mix, args.seconds),
               engine.prefill_chunk, cfg["vocab"], args.seed)
    tick, spent = engine.tick, []

    def timed():
        t0 = time.perf_counter()
        tick()
        spent.append((t0, time.perf_counter() - t0))

    engine.tick = timed
    for p in range(args.passes):
        tracing = p % 2 == 1
        engine.reset()
        spent.clear()
        served = now["served"] = serve.Served(
            t0=0.0, seconds=args.seconds, due={r.uid: r.due for r in reqs},
            submitted={}, tokens={}, times={}, ticks=0, lateness=[])
        if tracing:
            obs.enable(ring=1 << 20)
        serve.drive(engine, reqs, args.seconds, drain_s=mix["drain_s"],
                    drain=True, served=served)
        obs.disable()
        obs.clear()
        end = served.t0 + args.seconds
        inside = [d for t0, d in spent if t0 < end]
        e2e = serve.end_to_end(served, reqs, args.seconds)[0]
        print(json.dumps({"pass": p, "tracing": tracing,
                          "ticks": len(inside),
                          "tick_ms_mean": 1e3 * sum(inside) / len(inside),
                          **e2e}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
