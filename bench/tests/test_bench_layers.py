"""The program's layer names read from a trace (``benchlib.layers`` and
``bench/tools/layer_times.py``): on a hand-made trace with known answers,
on the benchmark's recorded trace of a program without names, on a trace
recorded on the chip with them, and on a traced run here."""

import importlib.util
import json
import os
import time

import pytest

from benchlib import layers, spec, trace as tr
from conftest import BENCH, ROOT

DATA = os.path.join(BENCH, "tests", "data")

# Two segment programs share an instruction name with different scopes;
# the migration's re-bin sits in sim.binning; jit_read is a program of the
# harness.  One chip, a window of 100 ns, two steps.
SPANS = {
    "devices": {"0": [
        ["fusion.1", 0, 10, "jit_segment_full(3)"],
        ["fusion.1", 20, 40, "jit_segment_delta(4)"],
        ["sort.2", 40, 45, "jit_segment_delta(4)"],
        ["sim_sweep_pairs.3", 45, 55, "jit_segment_delta(4)"],
        ["fusion.6", 57, 58, "jit_segment_delta(4)"],
        ["copy.4", 95, 97, "jit_segment_delta(4)"],
        ["reduce.5", 98, 99, "jit_read(5)"]]},
    "host": [["bench.call", 0, 100], ["sim.dispatch", 0, 5],
             ["sim.wait", 5, 60], ["sim.guards.host_check", 60, 90]],
}
SCOPES = {
    "jit_segment_full": {"sim.update": {"fusion.1"}},
    "jit_segment_delta": {"sim.aura": {"fusion.1"},
                          "sim.binning": {"sort.2"},
                          "sim.sweep.interior": {"sim_sweep_pairs.3"},
                          "sim.migration": {"fusion.6"}},
}
LAYER_METRICS = set(layers.DEVICE_LAYERS) | set(layers.HOST_LAYERS)


def _ctx(norm, **kw):
    ctx = {"trace": norm, "window": tr.window(norm), "steps": 2,
           "chips": len(norm["devices"]), "modules": set(),
           "kernels": set(), "sorts": set(), "collectives": set(),
           "pairs_per_step": None, "halo_bytes_per_step": []}
    ctx.update(kw)
    return ctx


def test_layer_times_read_each_program_by_its_scopes():
    got = layers.readings(SPANS, SCOPES, 2)
    ns = 1e-6   # ms; every reading is per step, over two steps
    # no op of sim.guards ran: its metric is left out
    assert set(got) == LAYER_METRICS - {"guard_device_ms_per_step"}
    assert got["sweep_ms_per_step"] == pytest.approx(10 / 2 * ns)
    assert got["aura_ms_per_step"] == pytest.approx(20 / 2 * ns)
    assert got["update_ms_per_step"] == pytest.approx(10 / 2 * ns)
    assert got["binning_ms_per_step"] == pytest.approx(5 / 2 * ns)
    assert got["migration_ms_per_step"] == pytest.approx(1 / 2 * ns)
    # the compiler's copy and the harness's own program
    assert got["unscoped_device_ms_per_step"] == pytest.approx(3 / 2 * ns)
    assert got["guard_host_ms_per_step"] == pytest.approx(30 / 2 * ns)


def test_an_op_of_no_known_module_has_no_scope():
    """Without a module an op's scope is known only where one program
    could have run it."""
    bare = {"devices": {"0": [[n, s, e, ""] for n, s, e, _ in
                              SPANS["devices"]["0"]]},
            "host": SPANS["host"]}
    w = tr.window(bare)
    assert layers.scoped_ns(bare, w, SCOPES, "sim.binning") is None
    one = {"jit_segment_delta": SCOPES["jit_segment_delta"]}
    assert layers.scoped_ns(bare, w, one, "sim.binning") == 5
    assert layers.scoped_ns(bare, w, one, "sim.aura") == 10 + 20


def test_an_idle_gap_takes_the_name_of_the_innermost_span_over_it():
    gaps = dict((round(t * 1e9), n)
                for n, t in layers.idle_gaps(SPANS, tr.window(SPANS)))
    assert gaps == {37: "sim.guards.host_check", 10: "sim.wait",
                    2: "sim.wait", 1: "bench.call"}


def test_enclosing_finds_the_module_event_over_an_op():
    mods = [(0.0, 10.0, "jit_a(1)"), (20.0, 30.0, "jit_b(2)")]
    assert [layers.enclosing(mods, t) for t in (0, 9.5, 10, 25, 31)] == \
        ["jit_a(1)", "jit_a(1)", "", "jit_b(2)", ""]
    assert layers.enclosing([], 5.0) == ""


def test_the_benchmark_recorded_trace_reads_no_layer_and_keeps_its_gaps():
    """The program before it named its layers, recorded on the chip: no
    layer reads, and every gap keeps the benchmark's own name."""
    with open(os.path.join(DATA, "trace_dense512_v5e.json")) as f:
        norm = json.load(f)
    w = tr.window(norm)
    assert layers.readings(norm, {"jit_seg": {}}, 2) == {}
    assert layers.idle_gaps(norm, w) == tr.idle_gaps(norm, w)
    assert [n for n, _ in layers.idle_gaps(norm, w)] == ["bench.call"] * 10


def test_the_layers_of_a_trace_recorded_on_the_chip_with_the_spans():
    """clustering.dense-512 on the chip with the program's scopes and
    spans: the benchmark's readers and the layers give what the chip run
    printed, and the longest idle gaps are the guards' host check."""
    with open(os.path.join(DATA, "trace_dense512_v5e_spans.json")) as f:
        rec = json.load(f)
    mods = rec["modules"]
    norm = {"devices": {d: [[n, s, e, mods[m]] for n, s, e, m in evs]
                        for d, evs in rec["devices"].items()},
            "host": rec["host"]}
    c = rec["context"]
    scopes = {m: {s: set(n) for s, n in sc.items()}
              for m, sc in rec["scopes"].items()}
    got = layers.readings(norm, scopes, c["steps"])
    assert set(got) == LAYER_METRICS
    ctx = _ctx(norm, steps=c["steps"], kernels=set(c["kernels"]),
               sorts=set(c["sorts"]), collectives=set(c["collectives"]),
               modules=set(c["modules"]), pairs_per_step=c["pairs_per_step"])
    for name, value in rec["readings"].items():
        read = got[name] if name in LAYER_METRICS else \
            spec.load_reader(name, ROOT).read(ctx)
        assert read == pytest.approx(value, rel=1e-9), name
    gaps = layers.idle_gaps(norm, ctx["window"])
    assert [n for n, _ in gaps[:2]] == ["sim.guards.host_check"] * 2
    assert gaps[0][1] == pytest.approx(0.250001279)
    # the guards' host time is the idle time the gaps put under its name
    gap_ms = sum(t for n, t in gaps if n == "sim.guards.host_check") * 1e3
    assert gap_ms / c["steps"] == pytest.approx(
        got["guard_host_ms_per_step"], rel=0.1)
    # the scopes cover all but a sliver of the device's busy time
    busy_ms = tr.busy_ns(norm, ctx["window"]) / 1e6 / c["steps"]
    assert got["unscoped_device_ms_per_step"] < 0.01 * busy_ms


def _tool():
    path = os.path.join(BENCH, "tools", "layer_times.py")
    sp = importlib.util.spec_from_file_location("layer_times", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def test_the_tool_reads_the_program_spans_of_a_traced_run(small_root,
                                                          fresh_programs):
    """A traced run of the one-chip cell on a small grid here: the result
    is the benchmark's, the guards' host check is read from the program's
    spans (no device plane here, so no device layer), and the record
    round-trips through JSON."""
    from benchlib import run

    tool = _tool()
    cell = spec.load_cell("clustering.dense-512", small_root)
    normalize, context = tr.normalize, run.trace_context
    res = tool.traced(cell, 20260013, 0.2, t_start=time.perf_counter(),
                      require_chip=False, root=small_root)
    assert (tr.normalize, run.trace_context) == (normalize, context)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert "breakdown" in res
    names = {n for n, _, _ in res["norm"]["host"]}
    assert {"bench.call", "sim.dispatch", "sim.wait",
            "sim.guards.host_check"} <= names
    assert set(res["layers"]) == {"guard_host_ms_per_step"}
    assert res["layers"]["guard_host_ms_per_step"] > 0
    assert set(res["scopes"]["jit_segment_full"]) >= {
        "sim.sweep", "sim.binning", "sim.guards"}
    rec = json.loads(json.dumps(tool.record(res, 20260013, cell.name,
                                            "cpu")))
    assert rec["readings"]["guard_host_ms_per_step"] == \
        res["layers"]["guard_host_ms_per_step"]
    assert rec["context"]["steps"] == res["attempted"]
