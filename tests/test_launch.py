"""Launcher/dry-run path on a small fake mesh (subprocess: 8 devices)."""

import json
import os
import subprocess
import sys
import tempfile
import textwrap


def test_dryrun_cell_builds_and_compiles_small_mesh():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, dataclasses
        import jax.numpy as jnp
        from repro.compat import make_mesh, set_mesh
        from repro.configs.base import get_arch, input_specs, ShapeSpec
        from repro.models import lm as lm_mod
        from repro.parallel import sharding as shd
        from repro.optim.adamw import AdamWConfig, adamw_init
        from repro.train.step import build_train_step
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = dataclasses.replace(get_arch("qwen2-1.5b").reduced(),
                                  n_model_shards=2)
        shape = ShapeSpec("tiny", "train", 64, 8)
        ap = jax.eval_shape(lambda k: lm_mod.init_lm(k, cfg),
                            jax.random.PRNGKey(0))
        ps = shd.param_shardings(ap, mesh)
        batch = input_specs(cfg, shape)
        bs = shd.batch_shardings(batch, mesh, ("data",))
        ocfg = AdamWConfig()
        astate = jax.eval_shape(
            lambda p: {"params": p, "opt": adamw_init(ocfg, p)}, ap)
        ssh = {"params": ps, "opt": {"m": ps, "v": ps,
               "step": NamedSharding(mesh, P())}}
        step = build_train_step(cfg, ocfg, mesh=mesh, dp_axes=("data",),
                                grad_accum=2)
        with set_mesh(mesh):
            c = jax.jit(step, in_shardings=(ssh, bs),
                        out_shardings=(ssh, None),
                        donate_argnums=(0,)).lower(astate, batch).compile()
        m = c.memory_analysis()
        assert m.temp_size_in_bytes > 0
        cost = c.cost_analysis()
        if isinstance(cost, (list, tuple)):   # one dict per device, old jax
            cost = cost[0]
        assert cost.get("flops", 0) > 0
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=560)
    assert r.returncode == 0, f"STDOUT:{r.stdout}\nSTDERR:{r.stderr}"
    assert "OK" in r.stdout


def test_production_mesh_shapes():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.mesh import (make_production_mesh, dp_axes_for,
                                       seq_axis_size)
        m1 = make_production_mesh()
        assert m1.axis_names == ("data", "model")
        assert m1.devices.shape == (16, 16)
        m2 = make_production_mesh(multi_pod=True)
        assert m2.axis_names == ("pod", "data", "model")
        assert m2.devices.shape == (2, 16, 16)
        assert dp_axes_for(m2) == ("pod", "data")
        assert seq_axis_size(m2) == 1
        m3 = make_production_mesh(seq_parallel=4)
        assert m3.axis_names == ("data", "seq", "model")
        assert m3.devices.shape == (4, 4, 16)
        assert seq_axis_size(m3) == 4 and dp_axes_for(m3) == ("data",)
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, f"STDOUT:{r.stdout}\nSTDERR:{r.stderr}"


def test_cell_matrix_covers_assignment():
    from repro.launch.dryrun import cell_matrix
    cells = cell_matrix()
    lm_cells = [c for c in cells if c[0] == "lm"]
    # 12 archs (10 assigned + 2 beyond: the GSPN LM, the granite hybrid)
    # × 4 shapes
    assert len(lm_cells) == 48
    skips = [c for c in lm_cells if c[3] is not None]
    assert len(skips) == 8            # long_500k × full-attention archs
    assert all(c[2] == "long_500k" for c in skips)
    vision = [c for c in cells if c[0] == "vision"]
    assert len(vision) == 2


def test_roofline_analysis_reads_records():
    from repro.roofline.analysis import analyze_dir, markdown_table
    rec = {
        "arch": "qwen2-1.5b", "shape": "train_4k", "mesh": "single",
        "status": "ok", "n_devices": 256,
        "meta": {"arch": "qwen2-1.5b", "shape": "train_4k",
                 "kind": "train", "family": "dense",
                 "seq_len": 4096, "global_batch": 256},
        "flops": 1e15, "bytes_hbm": 1e13, "bytes_hbm_calibrated": 8e12,
        "collectives": {"total": 1e11, "all-reduce": 1e11, "count": 3},
    }
    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "x__train_4k__single.json"), "w") as f:
            json.dump(rec, f)
        rows, skips, errors = analyze_dir(d, "single")
    assert len(rows) == 1 and not errors
    r = rows[0]
    assert r.dominant == "memory"           # 8e12/819e9 > 1e15/197e12
    assert 0 < r.useful_ratio < 10
    assert "qwen2-1.5b" in markdown_table(rows)
