"""The reduction from a trace to the per-layer metrics: on a hand-made
trace with known answers, and on a small trace recorded on the chip."""

import json
import os

import pytest

from benchlib import spec, trace as tr
from conftest import BENCH, ROOT

SYNTH = {
    "devices": {
        "0": [["custom-call.1", 0, 50, "jit_seg"],
              ["sort.2", 60, 70, "jit_seg"],
              ["fusion.3", 65, 80, "jit_seg"],
              ["all-reduce.4", 90, 100, "jit_seg"],
              ["all-reduce.4", 200, 300, "jit_read"]],
        "1": [["custom-call.1", 0, 30, "jit_seg(7)"],
              ["all-reduce.4", 40, 60, "jit_seg(7)"],
              ["fusion.3", 50, 100, "jit_seg(7)"]],
    },
    "host": [["bench.call", 0, 82], ["bench.readback", 82, 95],
             ["bench.call", 95, 100], ["bench.reset", 400, 410]],
}


def _ctx(norm, **kw):
    ctx = {"trace": norm, "window": tr.window(norm), "steps": 2,
           "chips": len(norm["devices"]), "modules": {"jit_seg"},
           "kernels": {"custom-call.1"}, "sorts": {"sort.2"},
           "collectives": {"all-reduce.4"}, "pairs_per_step": 1000.0,
           "halo_bytes_per_step": [10, 30]}
    ctx.update(kw)
    return ctx


def _read(name, ctx):
    return spec.load_reader(name, ROOT).read(ctx)


def test_window_busy_and_idle_on_a_hand_made_trace():
    w = tr.window(SYNTH)
    assert w == (0, 100)
    # chip 0 busy 0-50, 60-80, 90-100 = 80; chip 1 busy 0-30, 40-100 = 90
    assert tr.busy_ns(SYNTH, w) == pytest.approx(85.0)
    assert _read("device_idle_share", _ctx(SYNTH)) == pytest.approx(15.0)


def test_kernel_sort_and_exchange_times_on_a_hand_made_trace():
    ctx = _ctx(SYNTH)
    # kernel: (50 + 30) / 2 chips / 2 steps = 20 ns = 2e-5 ms
    assert _read("sweep_kernel_ms_per_step", ctx) == pytest.approx(2e-5)
    # sort only on chip 0: 10 / 2 / 2
    assert _read("binning_sort_ms_per_step", ctx) == pytest.approx(2.5e-6)
    # pairs: 2000 over 80 ns of kernel time summed over chips
    assert _read("sweep_pairs_per_s", ctx) == pytest.approx(2000 / 80e-9)
    # collective exposed: chip 0 90-100 (the jit_read one is outside the
    # window and module), chip 1 40-50 (50-60 overlaps fusion.3)
    assert _read("exchange_exposed_ms_per_step", ctx) == \
        pytest.approx((10 + 10) / 2 / 2 / 1e6)
    assert _read("halo_wire_bytes_per_step", ctx) == 20.0


def test_breakdown_names_gaps_by_the_host_span_over_them():
    w = tr.window(SYNTH)
    gaps = tr.idle_gaps(SYNTH, w)
    assert sorted(map(tuple, gaps)) == sorted([
        ("bench.call", 10e-9), ("bench.readback", 10e-9),
        ("bench.call", 10e-9)])
    ops = dict(tr.op_totals(SYNTH, w))
    assert ops["custom-call.1"] == pytest.approx(40e-9)
    assert ops["fusion.3"] == pytest.approx(32.5e-9)


def test_readers_find_nothing_without_their_ops():
    ctx = _ctx(SYNTH, kernels=set(), sorts=set(), collectives=set(),
               halo_bytes_per_step=[0, 0])
    for name in ("sweep_kernel_ms_per_step", "sweep_pairs_per_s",
                 "binning_sort_ms_per_step",
                 "exchange_exposed_ms_per_step",
                 "halo_wire_bytes_per_step"):
        assert _read(name, ctx) is None


RECORDED = os.path.join(BENCH, "tests", "data", "trace_dense512_v5e.json")
# the compiled step's HLO names these (clustering.dense-512 on a TPU v5
# lite): the Pallas kernel's tpu_custom_call and the binning's sorts
KERNEL = {"body.8"}
SORTS = {"sort.62", "sort.64", "sort.65", "sort.67", "sort.68", "sort.69",
         "sort.70", "sort.71", "sort.72", "sort.73", "sort.74", "sort.75"}


def test_the_reduction_on_a_trace_recorded_on_the_chip():
    with open(RECORDED) as f:
        norm = json.load(f)
    ctx = _ctx(norm, kernels=KERNEL, sorts=SORTS, collectives=set(),
               modules={"jit_seg"}, pairs_per_step=9.2e7)
    w = ctx["window"]
    assert (w[1] - w[0]) / 1e9 == pytest.approx(11.344134127)
    assert tr.busy_ns(norm, w) / 1e9 == pytest.approx(10.631028718)
    assert _read("device_idle_share", ctx) == pytest.approx(6.2861158, 1e-6)
    kernel_ms = _read("sweep_kernel_ms_per_step", ctx)
    assert kernel_ms == pytest.approx(3444.688943)
    assert _read("binning_sort_ms_per_step", ctx) == \
        pytest.approx(120.5954815)
    assert _read("sweep_pairs_per_s", ctx) == \
        pytest.approx(9.2e7 / (kernel_ms / 1e3))
    assert _read("exchange_exposed_ms_per_step", ctx) is None
    top = tr.op_totals(norm, w)
    assert top[0][0] == "body.8" and len(top) == 10
    gaps = tr.idle_gaps(norm, w)
    assert gaps[0][0] == "bench.call" and gaps[0][1] == \
        pytest.approx(0.418140282)
    # every op lies inside the window's clock: host and device agree
    for d, evs in norm["devices"].items():
        assert min(s for _, s, _, _ in evs) >= w[0]


def test_event_names_reduce_to_instruction_names():
    assert tr.op_name("%sort.62 = (s32[6]{0}, s32[6]{0}) sort(%a, %b), "
                      "dimensions={0}") == ("sort.62", "sort")
    assert tr.op_name("%while.50 = (s32[]{:T(128)}, f32[2]) while(%t)")[1] \
        in tr.CONTAINERS
    assert tr.op_name("body.8") == ("body.8", "")
