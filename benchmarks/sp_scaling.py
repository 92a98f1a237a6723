"""Spatial-sequence-parallel scaling ladder (DESIGN.md §8).

Ladder over 1/2/4/8 simulated devices × grid sizes, reporting per-scan
step time and the analytic collective traffic: the sp scan exchanges one
boundary column (plus, for the all-gather strategy, the compact (W, W)
transfer operator) instead of any full activation — the ``ratio`` column
is collective bytes over the bytes a naive activation gather would move.
The strategy each rung measures is resolved by
``SPConfig.resolved_strategy`` — the SAME rule production dispatch uses —
and the traffic model is parameterized on the wire dtype
(``boundary_dtype``), both pinned against the implementation by tests.

The ``overlap`` rungs measure the fused opposite-direction pair
(``gspn_scan_sp_pair``): one collective per pair (counted from the
jaxpr), with overlap efficiency derived from three schedules of the SAME
computation — ``overlap`` (production), ``serial`` (a barrier pins the
exchange ahead of the local scan: exchange fully exposed) and ``skip``
(no exchange: the timing floor):

    exposed = serial - skip          # exchange time on the critical path
    hidden  = serial - overlap       # how much of it overlap recovers
    overlap_efficiency = hidden / exposed

On a TPU host the whole ladder runs in this one process over the
chips it holds (1, 2, 4 devices of the host): a chip belongs to one
process, so no child may need it.  On the CPU, device counts are forced
with ``--xla_force_host_platform_device_count``, which must be set
BEFORE jax imports, so each rung runs in a child interpreter (``python
-m benchmarks.sp_scaling --devices N``) and the parent ``run()``
re-emits the children's CSV rows; a 2-host rung then repeats the
measurement on a true multi-PROCESS mesh (two coordinated children
through ``repro.compat.distributed_initialize``, gloo CPU collectives).
A rung that fails fails the ladder.  CPU timings are indicative only
(like fig3, the ladder is reproduced structurally); the traffic model
is exact.
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys

DEVICES = (1, 2, 4, 8)
GRIDS = [(2, 2, 256, 256), (2, 2, 512, 512)]    # (B, C_proxy, H, W)
SMOKE_DEVICES = (1, 2)
SMOKE_GRIDS = [(1, 2, 64, 64)]
MULTIHOST_PROCS = 2


def strategy_for(n_dev: int, *, pair: bool = False) -> str:
    """The strategy production resolves for this device count.

    Delegates to ``SPConfig.resolved_strategy`` so the ladder can never
    drift from what ``ops.py`` dispatch actually runs (a drift-pin test
    asserts the agreement across device counts).
    """
    from repro.parallel.gspn_sp import SPConfig
    return SPConfig(n_blocks=n_dev).resolved_strategy(pair=pair)


def collective_bytes(n_dev: int, b: int, g: int, w: int, strategy: str,
                     wire_bytes: int = 4) -> int:
    """Exact per-scan exchange traffic in ``wire_bytes``-wide payloads.

    ppermute ships boundary columns hop by hop; allgather ships the
    compact (T, b) pairs; pair_allgather ships BOTH directions' stacked
    (T, b) states plus the 3 adjoint edge weight rows in one collective
    (G_w = b: compact taps).  ``wire_bytes`` is the itemsize of the
    configured ``boundary_dtype`` (bf16 wire halves every figure).
    """
    if n_dev == 1:
        return 0
    if strategy == "ppermute":
        return (n_dev - 1) * g * w * wire_bytes
    if strategy == "allgather":
        return n_dev * (b * w * w + g * w) * wire_bytes
    if strategy == "pair_allgather":
        return n_dev * 2 * (b * w * w + g * w + 3 * b * w) * wire_bytes
    raise ValueError(f"unknown strategy {strategy!r}")


def _pair_inputs(b, cp, h, w):
    import jax.numpy as jnp
    from benchmarks.common import make_gspn_inputs

    x, wl0, wc0, wr0, lam0 = make_gspn_inputs(b, cp, h, w, seed=0)
    _, wl1, wc1, wr1, lam1 = make_gspn_inputs(b, cp, h, w, seed=1)
    return (x, jnp.stack([wl0, wl1]), jnp.stack([wc0, wc1]),
            jnp.stack([wr0, wr1]), jnp.stack([lam0, lam1]))


def _overlap_row(n_dev, tag, mesh, args, time_fn, wire_dtype="float32"):
    """Time the three exchange schedules of the fused pair and derive the
    overlap efficiency + jaxpr-counted collectives per pair."""
    import jax

    from repro.parallel.gspn_sp import (collectives_in_jaxpr,
                                        gspn_scan_sp_pair)

    times = {}
    for mode in ("overlap", "serial", "skip"):
        fn = jax.jit(lambda *a, m=mode: gspn_scan_sp_pair(
            *a, mesh=mesh, exchange_mode=m, boundary_dtype=wire_dtype))
        # the dtype-ladder precedent: relative timings get a few
        # iterations even under --smoke so one hiccup can't flip them
        times[mode] = time_fn(fn, *args, iters=10, min_iters=5)
    exposed = max(times["serial"] - times["skip"], 0.0)
    hidden = max(times["serial"] - times["overlap"], 0.0)
    eff = min(hidden / exposed, 1.0) if exposed > 0 else 0.0
    fused = collectives_in_jaxpr(
        lambda *a: gspn_scan_sp_pair(*a, mesh=mesh,
                                     boundary_dtype=wire_dtype), *args)
    per_dir = collectives_in_jaxpr(
        lambda *a: gspn_scan_sp_pair(*a, mesh=mesh, strategy="allgather",
                                     boundary_dtype=wire_dtype), *args)
    return (f"sp_scaling/{tag}", times["overlap"] * 1e6,
            f"strategy=pair_allgather;collectives_per_pair={len(fused)};"
            f"per_direction_collectives={len(per_dir)};"
            f"overlap_efficiency={eff:.3f};"
            f"serial_us={times['serial'] * 1e6:.1f};"
            f"floor_us={times['skip'] * 1e6:.1f};"
            f"exchange_exposed_us={exposed * 1e6:.1f};"
            f"exchange_hidden_us={hidden * 1e6:.1f};"
            # efficiency needs spare cores to hide I/O behind compute —
            # on a 1-core host every schedule serializes and ~0 is the
            # honest reading; multi-core runners show the real overlap
            f"host_cores={os.cpu_count()};"
            f"wire_dtype={wire_dtype}")


def _child(n_dev: int, smoke: bool) -> None:
    # appended so it wins over any inherited forced device count
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_dev}")
    import benchmarks.common as common
    common.SMOKE = smoke
    _rung(n_dev, smoke)


def _rung(n_dev: int, smoke: bool) -> None:
    """One device count's rows, on the first ``n_dev`` devices of this
    process."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import emit, time_fn, make_gspn_inputs
    from repro.launch.mesh import make_sp_mesh
    from repro.parallel.gspn_sp import gspn_scan_sp

    mesh = make_sp_mesh(n_dev) if n_dev > 1 else None
    strategy = strategy_for(n_dev)
    # bf16 wire only on the full ladder — smoke keeps one rung per shape
    wires = ("float32",) if smoke or n_dev == 1 else ("float32", "bfloat16")
    for b, cp, h, w in (SMOKE_GRIDS if smoke else GRIDS):
        x, wl, wc, wr, lam = make_gspn_inputs(b, cp, h, w)
        g = b * cp
        for wire in wires:
            fn = jax.jit(lambda *a, wd=wire: gspn_scan_sp(
                *a, mesh=mesh, strategy=strategy, boundary_dtype=wd))
            t = time_fn(fn, x, wl, wc, wr, lam)
            wire_bytes = jnp.dtype(wire).itemsize
            coll = collective_bytes(n_dev, b, g, w, strategy, wire_bytes)
            act = g * h * w * 4
            suffix = "" if wire == "float32" else "_bf16wire"
            emit(f"sp_scaling/dev{n_dev}_h{h}w{w}{suffix}_us", t * 1e6,
                 f"strategy={strategy if n_dev > 1 else 'local'};"
                 f"collective_bytes={coll};activation_bytes={act};"
                 f"ratio={coll / act:.5f};wire_dtype={wire}")
        if n_dev > 1:
            name, us, derived = _overlap_row(
                n_dev, f"overlap_dev{n_dev}_h{h}w{w}_us", mesh,
                _pair_inputs(b, cp, h, w), time_fn)
            emit(name, us, derived)


def _multihost_child(proc_id: int, port: int, smoke: bool) -> None:
    # One local device per process: the 2-process mesh IS the 2 hosts.
    # appended so it wins over any inherited forced device count
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=1")
    from repro import compat
    compat.distributed_initialize(f"localhost:{port}", MULTIHOST_PROCS,
                                  proc_id)
    import numpy as np
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import benchmarks.common as common
    common.SMOKE = smoke
    from benchmarks.common import emit, time_fn
    from repro.launch.mesh import make_sp_mesh

    mesh = make_sp_mesh(MULTIHOST_PROCS)
    b, cp, h, w = SMOKE_GRIDS[0] if smoke else GRIDS[0]
    # Same seeds on every process → identical host-local values; wrap
    # them as GLOBAL arrays sharded over the cross-process seq axis.
    local = _pair_inputs(b, cp, h, w)
    specs = (P(None, "seq", None),) + (P(None, None, "seq", None),) * 4
    args = tuple(
        jax.make_array_from_callback(
            a.shape, NamedSharding(mesh, s),
            lambda idx, a=a: np.asarray(a)[idx])
        for a, s in zip(local, specs))
    name, us, derived = _overlap_row(
        MULTIHOST_PROCS, f"overlap_hosts{MULTIHOST_PROCS}_h{h}w{w}_us",
        mesh, args, time_fn)
    if proc_id == 0:
        emit(name, us, f"{derived};hosts={MULTIHOST_PROCS}")
    compat.distributed_shutdown()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_multihost(smoke: bool):
    """Launch the coordinated 2-process overlap rung; return proc-0 rows.
    A child that fails (or a runtime without multiprocess CPU
    collectives) fails the ladder."""
    port = _free_port()
    procs = []
    for i in range(MULTIHOST_PROCS):
        cmd = [sys.executable, "-m", "benchmarks.sp_scaling",
               "--multihost-proc", str(i), "--port", str(port)]
        if smoke:
            cmd.append("--smoke")
        procs.append(subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                      stderr=subprocess.PIPE, text=True))
    outs = []
    for p in procs:
        try:
            outs.append(p.communicate(timeout=900))
        except subprocess.TimeoutExpired:
            p.kill()
            outs.append(p.communicate())
    if any(p.returncode != 0 for p in procs):
        err = "\n".join(f"--- process {i} (rc={p.returncode}):\n{o[1]}"
                        for i, (p, o) in enumerate(zip(procs, outs)))
        raise RuntimeError(f"sp_scaling multihost rung failed:\n{err}")
    return [ln for ln in outs[0][0].splitlines()
            if ln.startswith("sp_scaling/")]


def run() -> None:
    import jax

    import benchmarks.common as common

    devices = SMOKE_DEVICES if common.SMOKE else DEVICES
    if jax.default_backend() == "tpu":
        # One process holds the chips: rungs run here, over the devices
        # this host has (emit() already records and prints each row).
        for n_dev in devices:
            if n_dev <= len(jax.devices()):
                _rung(n_dev, common.SMOKE)
        return
    for n_dev in devices:
        cmd = [sys.executable, "-m", "benchmarks.sp_scaling",
               "--devices", str(n_dev)]
        if common.SMOKE:
            cmd.append("--smoke")
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=900)
        if r.returncode != 0:
            raise RuntimeError(
                f"sp_scaling child (devices={n_dev}) failed:\n{r.stderr}")
        for line in r.stdout.splitlines():
            if line.startswith("sp_scaling/"):
                common.ROWS.append(line)
                print(line, flush=True)
    for line in _run_multihost(common.SMOKE):
        common.ROWS.append(line)
        print(line, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0,
                    help="child mode: run the rung for this device count")
    ap.add_argument("--multihost-proc", type=int, default=-1,
                    help="multi-process child mode: this process's id")
    ap.add_argument("--port", type=int, default=0,
                    help="coordinator port for --multihost-proc")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if args.multihost_proc >= 0:
        _multihost_child(args.multihost_proc, args.port, args.smoke)
    elif args.devices:
        _child(args.devices, args.smoke)
    else:
        if args.smoke:
            import benchmarks.common as common
            common.SMOKE = True
        print("name,us_per_call,derived")
        run()


if __name__ == "__main__":
    main()
