"""Cells are found by name: a configuration, a traffic mix, limits and a
metric added as files, with entries in BENCHMARK.json, make a cell without
a change to any file of the harness.  And the harness refuses to run
without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_cell_of_the_benchmark_resolves():
    b = spec.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for w in b["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.chips == w["chips"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        assert set(cell.limits)
        for m in cell.per_layer:
            assert callable(spec.reader(m["name"]))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert m["workloads"]


def test_a_cell_added_as_files_alone_is_found(tmp_path):
    root = tmp_path
    (root / "bench" / "configs").mkdir(parents=True)
    for d in ("traffic", "limits", "metrics"):
        (root / "bench" / d).mkdir()
    b = spec.benchmark()
    b["configs"].append({"name": "toy", "source": "https://example.org/toy",
                         "file": "bench/configs/toy.json", "reduced": [],
                         "why": "a test"})
    b["workloads"].append({"name": "toy.burst", "config": "toy",
                           "traffic": "burst", "chips": 1, "why": "a test"})
    b["end_to_end"].append({"name": "toy_rate", "unit": "tokens/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["toy.burst"]})
    b["per_layer"].append({"name": "toy_share", "unit": "%",
                           "better": "higher", "source": "device_trace",
                           "layer": "device", "moves": "toy_rate",
                           "workloads": ["toy.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    (root / "bench/configs/toy.json").write_text('{"hidden_size": 8}')
    (root / "bench/traffic/burst.json").write_text(
        '{"job": "watched", "batch": 2}')
    (root / "bench/limits/toy.burst.json").write_text('{"loss_gap": 0.5}')
    (root / "bench/metrics/toy_share.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    cell = spec.cell("toy.burst", root=root)
    assert cell.config == {"hidden_size": 8}
    assert cell.traffic["job"] == "watched"
    assert cell.limits == {"loss_gap": 0.5}
    assert sorted(m["name"] for m in cell.end_to_end) == ["setup_s",
                                                          "toy_rate"]
    assert [m["name"] for m in cell.per_layer] == ["toy_share"]
    assert spec.reader("toy_share", root=root)(None) == 42.0


def _run_main(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/main.py", "--workload",
         "sc2-watched-steady", "--seed", str(2 ** 31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_the_harness_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run_main(spec.ROOT, env)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_the_harness_needs_the_program_beside_it(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run_main(tmp_path, env)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def _ctx(e2e, counters, trace):
    from types import SimpleNamespace as NS
    return NS(cell=spec.cell("sc2-fleet4-loaderburn"),
              outcome=NS(e2e=e2e, counters=counters), trace=trace,
              peak={"flops_bf16": 197e12, "hbm_bytes_s": 819e9}, chips=1)


def test_a_reader_with_nothing_to_read_returns_nothing():
    ctx = _ctx({}, {"seq_len": 2048}, None)
    for m in spec.benchmark()["per_layer"]:
        assert spec.reader(m["name"])(ctx) is None, m["name"]


def test_readers_read_the_trace_and_the_harness_counters():
    from bench import tracing as T
    trace = T.Summary(window_s=1.0, busy_s=0.25, chips=1, lo=0,
                      hi=10 ** 9, host=[], events={"/device:TPU:0": [
                          T.Event("%pattern_summary.1", 0, 2_000_000),
                          T.Event("%fusion.3", 2_000_000, 250_000_000)]})
    ctx = _ctx({"fleet_tokens_per_s": 1000.0},
               {"seq_len": 2048, "ticks": 4,
                "summarize_shapes": [(40, 300)] * 4,
                "localize_s": [0.001, 0.003], "diagnosis_windows": [1, 2]},
               trace)
    read = {m["name"]: spec.reader(m["name"])(ctx)
            for m in spec.benchmark()["per_layer"]
            if "sc2-fleet4-loaderburn" in m["workloads"]}
    assert read["device_idle.fleet"] == 75.0
    assert read["summarize_ms.fleet"] == 0.5
    assert 0 < read["pattern_summary_roofline"] < 100
    assert read["localize_ms.fleet"] == 2.0
    assert read["diagnosis_windows.fleet"] == 1.5
    assert 0 < read["fleet_mfu"] < 100
