"""A cell, a configuration, a mix, a driver and a per-layer metric dropped
in as files and entries are found by name: a later change adds them
without editing a file that is already there."""
import json
import os
import shutil

from bench import harness, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DRIVER = '''
import numpy as np
from bench import harness


class _Dev:
    platform, device_kind = "cpu", "cpu"


def run(ctx):
    return harness.Outcome(
        metrics={"toy_ops_per_s": float(ctx.cell.mix["rate_per_s"]),
                 "setup_s": 1.5},
        checks={"answers": (0, ctx.cell.config["limits"]["answers"])},
        attempted=3, failed=0, devices=[_Dev()], memory_peak_bytes=7,
        counters={"n": ctx.cell.config["n"]})
'''

METRIC = '''
def read(ctx):
    return 2.0 * ctx["counters"]["n"]
'''


def test_files_dropped_in_are_found(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = tmp_path / "bench"
    (b / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "driver": "toy_driver", "n": 21,
         "limits": {"answers": 0}}))
    (b / "traffic" / "toy_mix.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 12.5}))
    (b / "drivers" / "toy_driver.py").write_text(DRIVER)
    (b / "metrics" / "toy.layer_ms.py").write_text(METRIC)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "https://example.org",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "a toy"})
    spec["workloads"].append({"name": "toy.mix", "config": "toy",
                              "traffic": "toy_mix", "chips": 1,
                              "why": "a toy"})
    spec["end_to_end"].append({"name": "toy_ops_per_s", "unit": "ops/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["toy.mix"]})
    spec["per_layer"].append({"name": "toy.layer_ms", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "toy", "moves": "toy_ops_per_s",
                              "workloads": ["toy.mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.resolve("toy.mix", str(tmp_path))
    assert cell.config["n"] == 21 and cell.mix["rate_per_s"] == 12.5
    assert [m["name"] for m in cell.metrics_layer] == ["toy.layer_ms"]
    assert {m["name"] for m in cell.metrics_e2e} == {"toy_ops_per_s",
                                                     "setup_s"}
    read = harness.metric_reader("toy.layer_ms", str(tmp_path))
    assert read({"counters": {"n": 21}}) == 42.0

    res = run.run_cell("toy.mix", seed=1, seconds=1, trace=False,
                       root=str(tmp_path), require_tpu=False, cache=False)
    assert res["correct"] and res["attempted"] == 3
    assert res["metrics"] == {"toy_ops_per_s": {"value": 12.5,
                                                "unit": "ops/s"},
                              "setup_s": {"value": 1.5, "unit": "s"}}
    assert res["checks"] == {"answers": {"value": 0, "limit": 0}}


def test_every_cell_resolves():
    spec = harness.spec()
    for wl in spec["workloads"]:
        cell = harness.resolve(wl["name"])
        assert cell.driver.run
        assert any(m["name"] == "setup_s" for m in cell.metrics_e2e)
        assert len(cell.metrics_e2e) >= 2 and cell.metrics_layer
        for m in cell.metrics_layer:
            assert harness.metric_reader(m["name"])
        assert set(cell.config["limits"]) >= {"false_negatives", "fpr"}
