"""Record a profiler trace of a steady window and reduce it to numbers.

Recording: ``Recorder`` starts ``jax.profiler`` (host tracer at level 1,
no Python tracer), opens a ``bench.window`` span that marks the window,
and the jobs wrap their calls into each layer in spans of their own
(``HOST_SPANS``).  Reduction (``reduce``) reads the ``.xplane.pb`` with
``jax.profiler.ProfileData`` and keeps, inside the window:

* every device's busy time: the union of its ``XLA Ops`` intervals;
* device time per program (``XLA Modules``, by name without the
  fingerprint) and per operation;
* the Pallas kernels: custom calls to ``tpu_custom_call``.  In this
  program every one is a GSPN scan kernel (all go through
  ``kernels/gspn_scan.pallas_call``); their names do not reach the trace,
  which names each after the ``platform_dependent`` branch around it;
* idle gaps on device 0, each attributed to the innermost host span that
  covers its midpoint ("host.other" when none does).
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import pathlib
import shutil
import time

WINDOW = "bench.window"
HOST_SPANS = ("engine.tick", "submit", "generator.wait", "step.dispatch",
              "host.sync")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'

@dataclasses.dataclass
class TraceSummary:
    window_ns: float
    busy_ns: float                      # averaged over the devices used
    devices: int
    module_ns: dict                     # program name -> device ns
    module_calls: dict                  # program name -> number of runs
    op_ns: dict                         # "program/op" -> device ns (dev 0)
    kernel_ns: float                    # Pallas kernels, device 0
    kernel_calls: int
    idle_gaps: dict                     # host activity -> idle ns
    host_spans: list                    # (name, start_ns, dur_ns)

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / 1e9] for k, v in ops],
                "idle_gaps": [[k, v / 1e9] for k, v in gaps]}


class Recorder:
    """``with Recorder(dir): ...`` traces the block as one window."""

    def __init__(self, out_dir: pathlib.Path):
        self.out_dir = pathlib.Path(out_dir)
        self._span = None

    def __enter__(self):
        import jax
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        time.sleep(0.05)             # the tracer drops spans opened at once
        self._span = jax.profiler.TraceAnnotation(WINDOW)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self._span.__exit__(*exc)
        time.sleep(0.05)
        jax.profiler.stop_trace()
        return False

    def file(self) -> pathlib.Path:
        found = glob.glob(str(self.out_dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.out_dir}")
        return pathlib.Path(found[0])


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def load(path):
    """A ``.xplane.pb``, or one compressed with gzip (``.gz``)."""
    from jax.profiler import ProfileData
    path = pathlib.Path(path)
    if path.suffix == ".gz":
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(str(path))


def reduce(path, n_devices: int = 1) -> TraceSummary:
    pd = load(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW or e.name in HOST_SPANS:
                        host.append((e.name, e.start_ns, e.duration_ns))
    windows = [h for h in host if h[0] == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW} span in the host trace")
    w0 = windows[0][1]
    w1 = w0 + windows[0][2]
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = devices[:n_devices]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")

    def clip(s, d):
        return max(s, w0), min(s + d, w1)

    busy, module_ns, module_calls = [], collections.Counter(), \
        collections.Counter()
    op_ns, dev0 = collections.Counter(), []
    kernel_ns, kernel_calls = 0.0, 0
    for di, plane in enumerate(devices):
        lines = {line.name: line for line in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       e.name.split("(", 1)[0])
                      for e in (lines["XLA Modules"].events
                                if "XLA Modules" in lines else ()))
        starts = [m[0] for m in mods]

        def module_of(t):
            i = bisect.bisect_right(starts, t) - 1
            return mods[i][2] if i >= 0 and t <= mods[i][1] else "?"

        ivs = []
        for e in lines["XLA Ops"].events if "XLA Ops" in lines else ():
            s, t = clip(e.start_ns, e.duration_ns)
            if t <= s:
                continue
            ivs.append((s, t))
            if di == 0:
                name = e.name.partition(" = ")[0].lstrip("%")
                if KERNEL_TARGET in e.name:
                    kernel_ns += t - s
                    kernel_calls += 1
                    name = "gspn_scan_kernel"
                op_ns[f"{module_of(e.start_ns)}/{name}"] += t - s
        busy.append(sum(e - s for s, e in _merged(ivs)))
        if di == 0:
            dev0 = ivs
            for m0, m1, base in mods:
                s, t = clip(m0, m1 - m0)
                if t > s:
                    module_ns[base] += t - s
                    module_calls[base] += 1

    spans = [h for h in host if h[0] != WINDOW and h[1] + h[2] > w0
             and h[1] < w1]
    gaps = collections.Counter()
    edge = w0
    for s, e in _merged(dev0) + [[w1, w1]]:
        if s > edge:
            mid = (edge + s) / 2
            cover = [h for h in spans if h[1] <= mid <= h[1] + h[2]]
            who = min(cover, key=lambda h: h[2])[0] if cover else "host.other"
            gaps[who] += s - edge
        edge = max(edge, e)
    return TraceSummary(
        window_ns=w1 - w0, busy_ns=sum(busy) / len(busy),
        devices=len(devices), module_ns=dict(module_ns),
        module_calls=dict(module_calls), op_ns=dict(op_ns),
        kernel_ns=kernel_ns, kernel_calls=kernel_calls, idle_gaps=dict(gaps), host_spans=spans)
