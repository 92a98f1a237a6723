"""Find the serving knee once, by a sweep on the chip: the highest offered
rate at which the backlog does not grow over the window.

    python3 bench/tools/knee.py --traffic serve_chat --rates 6,8,10,12 \\
        --seconds 20 --seed 7

One engine, warmed once; for each rate the same mix is offered open-loop
for ``--seconds`` and the backlog (requests submitted and still without a
first token) is read at a third and at the end of the window.  Prints one
line per rate and a JSON summary last.  The cells then offer fixed rates
(``bench/traffic/*.json``); the benchmark's runs never search.
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
    ap.add_argument("--config", default="qwen2-1.5b-gspn")
    ap.add_argument("--traffic", default="serve_chat")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    from benchlib import registry, traffic
    from benchlib.harness import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("knee: no TPU", file=sys.stderr)
        return 3
    serve = registry.job("serve")
    cfg = registry.load_json(BENCH / "configs" / f"{args.config}.json")
    mix = registry.load_json(BENCH / "traffic" / f"{args.traffic}.json")
    rates = [float(r) for r in args.rates.split(",")]
    pcfg = serve.program_config(cfg)
    params = serve.make_weights(pcfg, args.seed)
    first = {}

    def stream(uid, tok):
        if uid >= 0 and uid not in first:
            first[uid] = time.perf_counter()

    engine = serve.make_engine(params, pcfg, mix, args.seed, stream=stream)
    lengths = set()
    for r in rates:
        m = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=r))
        lengths |= set(traffic.prompt_lengths(m, args.seconds))
    serve.warm(engine, sorted(lengths), engine.prefill_chunk, cfg["vocab"],
               args.seed)
    out = []
    for r in rates:
        m = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=r))
        reqs = traffic.serve_schedule(m, args.seconds, args.seed,
                                      cfg["vocab"])
        first.clear()
        engine.reset()
        served = serve.Served(t0=0.0, seconds=args.seconds, due={},
                              submitted={}, tokens={}, times={}, ticks=0,
                              lateness=[])
        serve.drive(engine, reqs, args.seconds, drain_s=0.0, drain=False,
                    served=served)

        def backlog(t):
            """Requests submitted by t seconds and still without a first
            token then."""
            return (sum(1 for x in served.submitted.values() if x <= t)
                    - sum(1 for x in first.values() if x - served.t0 <= t))

        ttft = sorted(first[q.uid] - (served.t0 + q.due) for q in reqs
                      if q.uid in first)
        row = {"rate": r, "requests": len(reqs),
               "backlog_third": backlog(args.seconds / 3),
               "backlog_end": backlog(args.seconds), "ticks": served.ticks,
               "ttft_p50_ms": 1e3 * ttft[len(ttft) // 2] if ttft else None,
               "ttft_max_ms": 1e3 * ttft[-1] if ttft else None}
        print(json.dumps(row), flush=True)
        out.append(row)
    print(json.dumps({"sweep": out, "seconds": args.seconds}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
