"""The benchmark's yardstick on the CPU: counts and peaks checked by hand
at small sizes, the traffic generator, the lookup by name, and the
contract of BENCHMARK.json."""

from __future__ import annotations

import json
import pathlib
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import counts, peaks, registry, traffic  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


# -- counts ----------------------------------------------------------------

@pytest.mark.parametrize("kernel,flops,nbytes", [
    # G=4 planes, G_w=2, 3x5 grid, f32: x 240 B, lam 2x240, taps 3x2x120,
    # out 2x240; 7 operations per output element per direction
    ("gspn_pair_fwd", 7 * 2 * 60, 240 + 480 + 720 + 480),
    # dy 2x240, taps 3x2x120, f32 adjoint out 2x240; 6 per element
    ("gspn_pair_bwd", 6 * 2 * 60, 480 + 720 + 480),
    ("gspn_scan_fwd", 7 * 60, 240 + 240 + 360 + 240),
])
def test_scan_call_counts_by_hand(kernel, flops, nbytes):
    c = counts.ScanCall(kernel, g=4, gw=2, h=3, w=5, stream_bytes=4,
                        out_bytes=4)
    assert c.flops() == flops
    assert c.bytes() == nbytes


def test_vision_scan_calls_per_step():
    cfg = {"img_size": 64, "dims": [8, 16], "depths": [1, 2],
           "proxy_dim": 2, "channel_shared": True}
    fwd = counts.vision_scan_calls(cfg, batch=3, train=False)
    # two pairs per block, 3 blocks; grids 16 then 8
    assert [(c.kernel, c.g, c.gw, c.h) for c in fwd] == \
        [("gspn_pair_fwd", 6, 3, 16)] * 2 + [("gspn_pair_fwd", 6, 3, 8)] * 4
    assert len(counts.vision_scan_calls(cfg, 3, train=True)) == 12


def test_vision_macs_by_hand():
    # 8x8 image, one stage of one block, dim 2, proxy 1, MLP ratio 1:
    # stem 2*2*16*1*2 = 128; block: LPUs 4*2*9*2 = 144, projections
    # 4*(2 + 24 + 16 + 2) = 176, scans 4*4*1*4 = 64, MLP 2*4*2*2 = 32;
    # head 2*1
    cfg = {"img_size": 8, "in_chans": 1, "n_classes": 1, "dims": [2],
           "depths": [1], "proxy_dim": 1, "mlp_ratio": 1.0,
           "channel_shared": True}
    assert counts.vision_macs(cfg) == 128 + 144 + 176 + 64 + 32 + 2
    assert counts.vision_flops_per_image(cfg, train=True) == 6 * 546


def test_vision_macs_is_the_programs_arithmetic():
    from repro.configs.gspn2_vision import GSPN2_T
    from repro.models.vision import vision_macs
    cfg = json.loads((BENCH / "configs" / "gspn2-t.json").read_text())
    assert counts.vision_macs(dict(cfg, img_size=224)) == vision_macs(GSPN2_T)


def test_lm_flops_per_token_by_hand():
    cfg = {"d_model": 4, "gspn_proxy_dim": 2, "d_ff": 8, "n_layers": 3,
           "vocab": 10}
    # mixer 8 + 12 + 4 + 32 + 8 = 64, SwiGLU 3*4*8 = 96, per layer 160
    assert counts.lm_flops_per_token(cfg, head=False) == 2 * 3 * 160
    assert counts.lm_flops_per_token(cfg, head=True) == 2 * (480 + 40)


def test_lm_weights_match_the_programs_count():
    from repro.configs import qwen2_1_5b_gspn
    from repro.models.lm import count_active_params
    cfg = json.loads((BENCH / "configs" / "qwen2-1.5b-gspn.json").read_text())
    n = count_active_params(qwen2_1_5b_gspn.full())
    assert counts.lm_flops_per_token(cfg, head=True) == 2 * n


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert (p.flops_per_s, p.hbm_bytes_per_s) == (197e12, 819e9)
    assert peaks.least_seconds(197e12, 0, p) == 1.0
    assert peaks.least_seconds(0, 819e9 * 2, p) == 2.0
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")


# -- traffic ---------------------------------------------------------------

MIX = json.loads((BENCH / "traffic" / "serve_chat.json").read_text())


def test_traffic_is_deterministic_for_a_seed():
    a = traffic.serve_schedule(MIX, 30, 2**31 + 77, 1000)
    b = traffic.serve_schedule(MIX, 30, 2**31 + 77, 1000)
    assert [(r.due, r.max_new_tokens, r.prompt.tolist()) for r in a] == \
        [(r.due, r.max_new_tokens, r.prompt.tolist()) for r in b]


def test_seeds_reorder_the_same_work():
    a = traffic.serve_schedule(MIX, 30, 5, 1000)
    b = traffic.serve_schedule(MIX, 30, 6, 1000)
    assert [r.due for r in a] != [r.due for r in b]
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)


def test_lengths_lie_on_the_grid_within_the_clips():
    reqs = traffic.serve_schedule(MIX, 40, 3, 1000)
    p = MIX["prompt"]
    lens = np.array([len(r.prompt) for r in reqs])
    assert np.all(lens % p["round_up_to"] == 0)
    assert lens.min() >= p["min"] and lens.max() <= p["max"]
    outs = np.array([r.max_new_tokens for r in reqs])
    assert outs.min() >= MIX["output"]["min"]
    assert outs.max() <= MIX["output"]["max"]
    due = [r.due for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 40
    assert len(reqs) == round(MIX["arrivals"]["rate_per_s"] * 40)
    assert set(traffic.prompt_lengths(MIX, 40)) == set(lens.tolist())


def test_percentile():
    assert traffic.percentile(range(1, 102), 95) == 96.0
    assert traffic.percentile([3.0], 95) == 3.0


# -- lookup by name ----------------------------------------------------------

def test_cells_configs_traffic_and_readers_are_found_by_name():
    for w in SPEC["workloads"]:
        cell = registry.find_cell(w["name"])
        assert cell.config["name"] == w["config"]
        registry.job(cell.traffic["job"])
        registry.reference(cell.config["model"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            registry.metric_reader(m["name"])


def test_an_added_cell_needs_only_new_files_and_entries(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    (tmp_path / "bench" / "configs" / "tiny-lm.json").write_text(
        json.dumps({"name": "tiny-lm", "model": "gspn_lm"}))
    (tmp_path / "bench" / "traffic" / "burst.json").write_text(
        json.dumps({"job": "serve"}))
    (tmp_path / "bench" / "metrics" / "burst_share.py").write_text(
        "def read(run):\n    return 1.0\n")
    spec["configs"].append({"name": "tiny-lm", "source": "x",
                            "file": "bench/configs/tiny-lm.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "tiny.burst", "config": "tiny-lm",
                              "traffic": "burst", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "burst_share", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "serve engine",
                              "moves": "ttft_p50_ms",
                              "workloads": ["tiny.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = registry.find_cell("tiny.burst", tmp_path)
    assert cell.traffic == {"job": "serve"}
    assert [m["name"] for m in cell.per_layer] == ["burst_share"]
    assert registry.metric_reader("burst_share", tmp_path).read(None) == 1.0
    assert registry.job("serve", tmp_path).run
    for p, data in before.items():
        assert p.read_bytes() == data


# -- runs without a chip -----------------------------------------------------

def _run(cwd, env_extra=None):
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert "TPU" in r.stderr


def test_a_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]


# -- the contract of BENCHMARK.json ------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_benchmark_json_keeps_the_contract():
    s = SPEC
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert s["paths"] == ["bench"] and s["command"][1] == "bench/run.py"
    assert 1 <= s["run_seconds"] <= 51
    # a full check of 24 cells fits the budget
    assert (2 + 14 * 24) * (s["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    cells = {w["name"]: w for w in s["workloads"]}
    e2e = {m["name"]: m for m in s["end_to_end"]}
    names = [x["name"] for x in s["configs"]] + list(cells) + list(e2e) + \
        [m["name"] for m in s["per_layer"]]
    assert all(NAME.match(n) for n in names)
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert _line(c["why"]) and _line(c["source"])
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and _line(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in s["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= set(cells)
    layers = {}
    for m in s["per_layer"]:
        assert UNIT.match(m["unit"]) and _line(m["layer"])
        assert m["moves"] in e2e and set(m["workloads"]) <= set(cells)
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", cells)
        layers.setdefault(m["layer"], []).append(m["name"])
    for w in cells:
        reported = [m for m in s["end_to_end"]
                    if w in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(w in m["workloads"] for m in s["per_layer"])
    assert len(json.dumps(s)) < 64 * 1024
