"""Print the serve engine's ticks as a traced run's profiler trace shows
them (``benchlib.ticks``).

    python3 bench/tools/ticks.py <trace file or directory> ...

For each ``.xplane.pb`` or ``.xplane.pb.gz`` named, or found under a
directory named: the ticks, device idle per tick, programs started per
tick, device 0's idle time by the innermost span around it, and the
programs started inside the ticks by name.  One JSON line per trace.  A
traced run of the serving cell leaves its trace under
``.bench_cache/trace/<cell>/``.
"""

from __future__ import annotations

import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    from benchlib import ticks
    argv = sys.argv[1:] if argv is None else argv
    paths = []
    for a in map(pathlib.Path, argv):
        paths += (sorted(a.glob("**/*.xplane.pb*")) if a.is_dir() else [a])
    if not paths:
        print("ticks: no trace named or found", file=sys.stderr)
        return 2
    for p in paths:
        print(json.dumps({"trace": str(p),
                          **ticks.reduce(p).breakdown(top=40)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
