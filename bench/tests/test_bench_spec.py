"""A cell, a configuration, a mix and a per-layer metric are added by
adding files and BENCHMARK.json entries; nothing that exists is edited."""

import hashlib
import json
import os
import shutil
import time

from benchlib import run, spec
from conftest import make_root


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_cells_of_the_benchmark_load(small_root):
    with open(os.path.join(small_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], small_root)
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} >= {
            "setup_s", "agent_updates_per_s"}
        assert cell.per_layer
        assert hasattr(cell.reference, "run")
        for m in cell.per_layer:
            assert callable(spec.load_reader(m["name"], small_root).read)


def test_added_config_mix_and_metric_are_found_by_name(tmp_path):
    root = make_root(str(tmp_path))
    before = _digests(root)
    b = os.path.join(root, "bench")
    shutil.copy(os.path.join(b, "configs", "cell_clustering.json"),
                os.path.join(b, "configs", "sparse_sheet.json"))
    shutil.copy(os.path.join(b, "configs", "cell_clustering.py"),
                os.path.join(b, "configs", "sparse_sheet.py"))
    with open(os.path.join(b, "traffic", "sparse-64.json"), "w") as f:
        json.dump({"interior": [8, 8], "mesh_shape": [1, 1],
                   "steps_per_call": 2, "span_steps": 10,
                   "check_steps": 2}, f)
    with open(os.path.join(b, "limits", "sparse.sparse-64.json"), "w") as f:
        json.dump({"limits": {"agents_missing": 0}}, f)
    with open(os.path.join(b, "metrics", "calls_traced.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx['steps'])\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "sparse_sheet", "source": "x",
                             "file": "bench/configs/sparse_sheet.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "sparse.sparse-64",
                               "config": "sparse_sheet",
                               "traffic": "sparse-64", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "calls_traced", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "facade",
                               "moves": "agent_updates_per_s",
                               "workloads": ["sparse.sparse-64"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = spec.load_cell("sparse.sparse-64", root)
    assert cell.traffic["steps_per_call"] == 2
    assert cell.limits["limits"] == {"agents_missing": 0}
    assert "calls_traced" in [m["name"] for m in cell.per_layer]
    got = spec.read_metrics([m for m in cell.per_layer
                             if m["name"] == "calls_traced"],
                            {"steps": 7}, root)
    assert got == {"calls_traced": {"value": 7.0, "unit": "steps"}}
    # the new metric is not read in cells it does not list
    old = spec.load_cell("clustering.dense-512", root)
    assert "calls_traced" not in [m["name"] for m in old.per_layer]
    after = _digests(root)
    assert all(after[k] == v for k, v in before.items())


def test_a_three_dimensional_cell_is_added_by_files_and_runs(
        tmp_path, fresh_programs):
    """A 3-D configuration (the 27-cell stencil a spheroid needs) and its
    mix run through the harness and the reference unedited."""
    root = make_root(str(tmp_path))
    before = _digests(root)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "cell_clustering.json")) as f:
        cfg = json.load(f)
    cfg.update(name="clustering_3d", agents_per_cell=3.0)
    with open(os.path.join(b, "configs", "clustering_3d.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(b, "configs", "cell_clustering.py"),
                os.path.join(b, "configs", "clustering_3d.py"))
    with open(os.path.join(b, "traffic", "cube-6.json"), "w") as f:
        json.dump({"interior": [6, 6, 6], "mesh_shape": [1, 1, 1],
                   "steps_per_call": 1, "span_steps": 4,
                   "check_steps": 2}, f)
    with open(os.path.join(b, "limits", "clustering_3d.cube-6.json"),
              "w") as f:
        json.dump({"limits": {"agents_missing": 0,
                              "pos_mismatch_share": 0.002}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "clustering_3d", "source": "x",
                             "file": "bench/configs/clustering_3d.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "clustering_3d.cube-6",
                               "config": "clustering_3d",
                               "traffic": "cube-6", "chips": 1, "why": "x"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = spec.load_cell("clustering_3d.cube-6", root)
    res = run.run(cell, 20260011, 0.2, False, t_start=time.perf_counter(),
                  require_chip=False, root=root)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    after = _digests(root)
    assert all(after[k] == v for k, v in before.items())


def test_a_reader_that_finds_nothing_leaves_its_metric_out(small_root):
    ctx = {"trace": {"devices": {}, "host": []}, "window": None,
           "steps": 0, "kernels": set(), "sorts": set(),
           "collectives": set(), "modules": set(), "chips": 1,
           "pairs_per_step": None, "halo_bytes_per_step": []}
    cell = spec.load_cell("clustering.dense-512", small_root)
    assert spec.read_metrics(cell.per_layer, ctx, small_root) == {}
