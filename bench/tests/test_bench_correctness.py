"""The comparison that decides ``correct``, run through the harness at a
size a CPU test holds: a sound run passes; the control (the reference in
bfloat16 put in the program's place) and each fault the cells can have,
planted underneath the timed path, come out not correct."""

import json
import os
import subprocess
import sys
import textwrap
import time

import jax.numpy as jnp

import calibrate
from benchlib import compare, run, spec, traffic
from conftest import BENCH, ROOT


def _run(root, workload, seed=20260001):
    cell = spec.load_cell(workload, root)
    return run.run(cell, seed, 0.2, False, t_start=time.perf_counter(),
                   require_chip=False, root=root)


def test_a_sound_run_is_correct(small_root, fresh_programs):
    res = _run(small_root, "clustering.dense-512")
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "agent_updates_per_s",
                                   "hbm_bytes_per_agent"}


def test_the_bfloat16_control_fails_the_committed_limits(small_root,
                                                         fresh_programs):
    cell = spec.load_cell("clustering.dense-512", small_root)
    cfg, mix = cell.config, cell.traffic
    seed = 20260002
    pos, attrs = traffic.draw_agents(cfg, mix, seed)
    sim = run.build_sim(cell)
    sim.init(pos, attrs, seed=traffic.engine_seed(seed))
    kept = [sim.state]
    for _ in range(mix["check_steps"]):
        sim.run(1)
        kept.append(sim.state)
    answers = [compare.answer_of(s, 2) for s in kept]
    ctrl = calibrate.control_numbers(cell, pos, attrs,
                                     traffic.engine_seed(seed), answers)
    limits = {k: v for k, v in cell.limits["limits"].items() if k in ctrl}
    judged = compare.judge(ctrl, limits)
    assert not all(c["ok"] for c in judged.values()), judged


def test_a_step_that_returns_its_state_unchanged_is_caught(
        small_root, fresh_programs, monkeypatch):
    monkeypatch.setattr(fresh_programs.Engine, "local_step",
                        lambda self, state, comm, full_halo: state)
    res = _run(small_root, "clustering.dense-512")
    assert not res["correct"]
    assert res["checks"]["pos_mismatch_share"]["value"] > 0.5


def test_half_the_agents_left_out_of_the_sweep_is_caught(
        small_root, fresh_programs, monkeypatch):
    real = fresh_programs.sweep_accumulate

    def half(geom, soa, *a, **kw):
        acc = real(geom, soa, *a, **kw)
        keep = jnp.arange(geom.cap) < geom.cap // 2
        return {k: v * keep.reshape((1,) * geom.ndim + (-1,)
                                    + (1,) * (v.ndim - geom.ndim - 1))
                for k, v in acc.items()}

    monkeypatch.setattr(fresh_programs, "sweep_accumulate", half)
    res = _run(small_root, "clustering.dense-512")
    assert not res["correct"]


def test_an_answer_altered_where_it_is_produced_is_caught(
        small_root, fresh_programs, monkeypatch):
    real = fresh_programs.Engine.local_step

    def altered(self, state, comm, full_halo):
        out = real(self, state, comm, full_halo)
        attrs = dict(out.soa.attrs, pos=out.soa.attrs["pos"] + 1e-3)
        return fresh_programs.SimState(
            soa=out.soa.replace(attrs=attrs), refs=out.refs, it=out.it,
            key=out.key, gid_counter=out.gid_counter, dropped=out.dropped,
            halo_bytes=out.halo_bytes, codec_overflow=out.codec_overflow,
            health=out.health)

    monkeypatch.setattr(fresh_programs.Engine, "local_step", altered)
    res = _run(small_root, "clustering.dense-512")
    assert not res["correct"]


TWO_BY_TWO = """
import json, sys, time
sys.path[:0] = [{tests!r}, {bench!r}, {src!r}]
from conftest import make_root
from repro.core import compile_cache
compile_cache.enable_persistent_cache = lambda: "off"
import calibrate
from benchlib import run, spec
from repro.core import engine

def once(root):
    cell = spec.load_cell("clustering.2x2-512", root)
    for name in ("_cached_segment_runner", "_cached_sharded_step"):
        getattr(engine, name).cache_clear()
    res = run.run(cell, {seed}, 1.0, False, t_start=time.perf_counter(),
                  require_chip=False, root=root)
    return {{"correct": res["correct"], "attempted": res["attempted"],
             "checks": res["checks"]}}

sound = once(make_root({sound!r}, cells={cells}))
calibrate.plant_no_exchange()
faulty = once(make_root({faulty!r}, cells={cells}))
print(json.dumps({{"sound": sound, "faulty": faulty}}))
"""


def test_the_exchange_between_chips_left_out_is_caught(tmp_path):
    """The committed 2x2 mix (int8 delta codec, overlap auto) on 128x128
    cells, sound and with the exchange left out.  A sound run matches the
    reference to the ulp after its first step, which takes a full
    exchange, and stays within the codec's error after the second; the
    fault moves every agent next to a seam at once, which the first
    step's number catches at any share of agents next to a seam."""
    code = TWO_BY_TWO.format(
        tests=os.path.join(BENCH, "tests"), bench=BENCH,
        src=os.path.join(ROOT, "src"), sound=str(tmp_path / "sound"),
        faulty=str(tmp_path / "faulty"), cells=128, seed=20260005)
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    sound, faulty = got["sound"], got["faulty"]
    assert sound["correct"] and sound["attempted"] >= 2, sound
    assert sound["checks"]["first_step_mismatch_share"]["value"] == 0.0
    assert not faulty["correct"], faulty
    assert not faulty["checks"]["first_step_mismatch_share"]["ok"], faulty
