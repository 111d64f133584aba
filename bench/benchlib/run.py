"""One run of one cell: set-up, a measured window through the program's
normal path, an optional traced window, and the comparison with the plain
reference that decides ``correct``.

The window calls ``Simulation.run(steps_per_call)`` on a facade built with
``repro.sims.common.make_sim`` from the family's own ``behavior()``, with
the sweep backend ``auto``.  Every ``span_steps`` steps the facade goes
back to the initial state through ``Simulation.with_state`` (no host
transfer, no recompilation), so every run measures the same trajectory
whatever the speed, and a faster program never walks the state into a
regime (condensed clusters past the cell capacity) that the slower one
never reached.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List

import numpy as np

from benchlib import compare as cmp
from benchlib import hlo, spec as specmod, trace as tr
from benchlib import traffic as traffic_mod
from benchlib.cells import pair_count

TRACE_SECONDS = 10.0   # least traced window; it also holds every checked step


class NoChip(Exception):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads, by listening to
    the events JAX reports for them."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.cache_loads = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, name, _dur, **_kw):
        if name == self.COMPILE:
            self.compiles += 1
        elif name == self.CACHE_LOAD:
            self.cache_loads += 1

    def snapshot(self):
        return (self.compiles, self.cache_loads)


def device_info(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} x {devs[0].platform} "
                     f"({devs[0].device_kind})")
    return devs[:chips]


def build_sim(cell: specmod.Cell):
    """The deployment as a user builds it: the family's behavior() through
    make_sim, guards and scheduled reducers from the configuration."""
    from repro.core import operations
    from repro.sims.common import make_sim

    cfg, mix = cell.config, cell.traffic
    family = importlib.import_module(f"repro.sims.{cfg['sim']}")
    mesh_shape = tuple(mix["mesh_shape"])
    sim = make_sim(
        family.behavior(**cfg["behavior"]),
        interior=tuple(mix["interior"]), mesh_shape=mesh_shape,
        cell_size=float(cfg["cell_size"]), cap=int(cfg["cap"]),
        boundary=cfg["boundary"], delta=mix.get("delta"),
        dt=float(cfg["dt"]), sweep_backend="auto",
        overlap=mix.get("overlap", "auto"), guards=cfg.get("guards"))
    for op in cfg.get("ops", []):
        fn = getattr(operations, op["op"])(*op.get("args", []))
        sim.every(int(op["every"]), fn, name=op["name"])
    return sim


def _readback_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def read(state):
        return (jnp.sum(state.dropped), jnp.sum(state.health),
                jnp.sum(state.soa.valid, dtype=jnp.int32),
                jnp.sum(state.halo_bytes))

    return read


def programs_run(sim, s0, steps_per_call: int):
    """The compiled segment programs the window drives (the delta program
    too where the codec is on), as compiled for this device."""
    progs = sim.engine.make_segment_runner(sim.mesh).programs
    keys = [True, False] if sim.engine.delta_cfg.enabled else [True]
    return {k: progs[k].lower(s0, np.int32(steps_per_call)).compile()
            for k in keys}


def hbm_bytes_per_agent(stats: List[Any], agents_per_chip: List[int]
                        ) -> float:
    """Per chip, arguments + outputs - aliased + temporaries + generated
    code of the largest program the window ran, over the agents that chip
    owns; the largest over chips.  (One SPMD program: every chip holds the
    same bytes.)"""
    per_chip = max(s.argument_size_in_bytes + s.output_size_in_bytes
                   - s.alias_size_in_bytes + s.temp_size_in_bytes
                   + s.generated_code_size_in_bytes for s in stats)
    return per_chip / max(min(agents_per_chip), 1)


def agents_per_chip(state, mesh_shape) -> List[int]:
    """Live agents in each device's block of the global slot grid."""
    blocks = [np.asarray(state.soa.valid)]
    for axis, m in enumerate(mesh_shape):
        blocks = [b for blk in blocks
                  for b in np.array_split(blk, int(m), axis=axis)]
    return [int(b.sum()) for b in blocks]


def updates_per_s(agent_steps: int, seconds: float) -> float:
    return agent_steps / seconds


def run(cell: specmod.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True,
        root: str = specmod.ROOT) -> Dict[str, Any]:
    """Everything but printing: returns the result object."""
    import jax

    from repro.core.compile_cache import enable_persistent_cache

    devs = device_info(cell.chips, require_chip)
    counter = CompileCounter()
    cache_dir = enable_persistent_cache()
    log(f"[bench] {cell.name}: platform {devs[0].platform}, device_kind "
        f"{devs[0].device_kind}, devices {len(devs)}; compile cache "
        f"{cache_dir}")
    cfg, mix = cell.config, cell.traffic
    spc, span = int(mix["steps_per_call"]), int(mix["span_steps"])
    check = int(mix["check_steps"])
    if check % spc or span % spc or check > span:
        raise ValueError("steps_per_call must divide check_steps and "
                         "span_steps, and check_steps <= span_steps")
    if int(np.prod(mix["mesh_shape"])) != cell.chips:
        raise ValueError("mesh_shape does not match the cell's chips")

    sim = build_sim(cell)
    pos, attrs = traffic_mod.draw_agents(cfg, mix, seed)
    eseed = traffic_mod.engine_seed(seed)
    n = len(pos)
    sim.init(pos, attrs, seed=eseed)
    s0 = sim.state
    jax.block_until_ready(s0)
    per_chip = agents_per_chip(s0, mix["mesh_shape"])
    log(f"[bench] {n} agents ({per_chip} per chip), init "
        f"{time.perf_counter() - t_start:.2f} s from process start")

    read = _readback_fn()
    # warm-up: every program the window runs, then back to s0
    warm_calls = 2 if sim.engine.delta_cfg.enabled else 1
    for _ in range(warm_calls):
        sim.run(spc)
    jax.device_get(read(sim.state))
    sim.with_state(sim.engine, s0)
    compiled = programs_run(sim, s0, spc)
    stats = [c.memory_analysis() for c in compiled.values()]
    hbm = hbm_bytes_per_agent(stats, per_chip)
    c0 = counter.snapshot()
    setup_s = time.perf_counter() - t_start
    log(f"[bench] set-up {setup_s:.3f} s; compilations in set-up "
        f"{c0[0]}, persistent-cache loads {c0[1]}; hbm bytes per agent "
        f"{hbm:.1f}")

    trace_dir = os.path.join(root, ".bench_trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    window = TRACE_SECONDS if trace else float(seconds)
    kept: List = [s0]               # program states at steps 0..check
    traced_inputs: List = []
    halo_bytes: List[int] = []
    attempted = failed = agent_steps = 0
    in_span, live = 0, n
    marks = (0, 0)
    t0 = time.perf_counter()
    while True:
        if in_span + spc > span:
            with jax.profiler.TraceAnnotation("bench.reset"):
                sim.with_state(sim.engine, s0)
            in_span, marks = 0, (0, 0)
        if trace and len(traced_inputs) < 8:
            traced_inputs.append(sim.state)
        with jax.profiler.TraceAnnotation("bench.call"):
            sim.run(spc)
        with jax.profiler.TraceAnnotation("bench.readback"):
            dropped, health, live_now, hb = (
                int(x) for x in jax.device_get(read(sim.state)))
        attempted += spc
        agent_steps += live * spc
        live = live_now
        halo_bytes.append(hb)
        if dropped > marks[0] or health > marks[1]:
            failed += spc
        marks = (dropped, health)
        in_span += spc
        if attempted == in_span and in_span <= check:
            kept.append(sim.state)
        elapsed = time.perf_counter() - t0
        if elapsed >= window and (not trace or attempted >= check):
            break
    c1 = counter.snapshot()
    in_window = (c1[0] - c0[0], c1[1] - c0[1])
    log(f"[bench] window {elapsed:.3f} s, {attempted} steps, {failed} "
        f"failed; compilations in the window {in_window[0]}, "
        f"persistent-cache loads in the window {in_window[1]}")
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               if d.memory_stats() else 0 for d in devs)

    result: Dict[str, Any] = {"attempted": attempted, "failed": failed}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    if trace:
        jax.profiler.stop_trace()
        norm = tr.normalize(trace_dir, {d.id for d in devs})
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = trace_context(cell, sim, compiled, norm, attempted,
                            traced_inputs, halo_bytes)
        metrics = specmod.read_metrics(cell.per_layer, ctx, root)
        w = ctx["window"]
        device["busy_s"] = tr.busy_ns(norm, w) / 1e9 if w else 0.0
        device["window_s"] = (w[1] - w[0]) / 1e9 if w else 0.0
        result["breakdown"] = {"device_ops": tr.op_totals(norm, w),
                               "idle_gaps": tr.idle_gaps(norm, w)}
    else:
        values = {"setup_s": setup_s,
                  "agent_updates_per_s": updates_per_s(agent_steps,
                                                       elapsed),
                  "hbm_bytes_per_agent": hbm}
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    if in_window[0] or in_window[1]:
        log("[bench] a program compiled or loaded inside the window")

    # correctness: the program's answers at steps 1..check of the first
    # span, against the reference run from the drawn population
    ndim = len(mix["interior"])
    names = list(cfg.get("compare_attrs", []))
    t1 = time.perf_counter()
    answers = [cmp.answer_of(s, ndim, names) for s in kept]
    del sim, s0, kept, traced_inputs, compiled
    gc.collect()
    t2 = time.perf_counter()
    checks = correctness(cell, pos, attrs, eseed, answers, failed)
    log(f"[bench] answers to the host {t2 - t1:.2f} s, reference and "
        f"comparison {time.perf_counter() - t2:.2f} s")
    result.update(metrics=metrics, device=device, checks=checks,
                  correct=all(c["ok"] for c in checks.values()))
    return result


def slots_by_id(answers: List[Dict], n: int) -> List[np.ndarray]:
    """Per state, the slot of agent id 0..n-1 (missing agents: slot 0)."""
    out = []
    for a in answers:
        s = np.zeros((n, a["slot"].shape[1]), np.int64)
        ok = (a["ids"] >= 0) & (a["ids"] < n)
        s[a["ids"][ok]] = a["slot"][ok]
        out.append(s)
    return out


def reference_numbers(cell: specmod.Cell, pos, attrs, eseed: int,
                      answers: List[Dict], dtype: str = "float32"
                      ) -> Dict[str, float]:
    cfg, mix = cell.config, cell.traffic
    steps = len(answers) - 1
    ctx = {"engine_seed": eseed, "slots": slots_by_id(answers, len(pos))}
    ref = cell.reference.run(cfg, mix, pos, attrs, steps, ctx, dtype=dtype)
    size = traffic_mod.domain_size(cfg, mix)
    tor = [cfg["boundary"] == "toroidal"] * len(size)
    mags = cmp.magnitudes(np.asarray(pos), ref)
    return cmp.summary([
        cmp.compare(a, r, size, tor, cfg.get("compare_attrs", []), t + 1,
                    mags[t])
        for t, (a, r) in enumerate(zip(answers[1:], ref))])


def correctness(cell, pos, attrs, eseed, answers, failed
                ) -> Dict[str, Dict[str, float]]:
    numbers = reference_numbers(cell, pos, attrs, eseed, answers)
    numbers["failed_steps"] = float(failed)
    limits = dict(cell.limits["limits"])
    limits.setdefault("failed_steps", 0)
    return cmp.judge(numbers, limits)


def trace_context(cell, sim, compiled, norm, steps, traced_inputs,
                  halo_bytes) -> Dict[str, Any]:
    """What the per-layer readers may read."""
    texts = [c.as_text() for c in compiled.values()]
    cfg, mix = cell.config, cell.traffic
    grid = traffic_mod.global_cells(cfg, mix)
    pairs = []
    for s in traced_inputs:
        v = np.asarray(s.soa.valid).ravel()
        p = np.asarray(s.soa.attrs["pos"]).reshape(-1, len(grid))[v]
        pairs.append(pair_count(p, float(cfg["cell_size"]), grid,
                                cfg["boundary"] == "toroidal"))
    sorts = set().union(*(hlo.names_by_opcode(t, {"sort"}) for t in texts))
    ctx = {
        "trace": norm,
        "window": tr.window(norm),
        "steps": steps,
        "chips": cell.chips,
        "modules": {hlo.module_name(t) for t in texts},
        "kernels": set().union(*(hlo.kernel_names(t) for t in texts)),
        "sorts": sorts if sorts_only_in_binning(sim, traced_inputs)
        else set(),
        "collectives": set().union(*(
            hlo.names_by_opcode(t, hlo.COLLECTIVES) for t in texts)),
        "pairs_per_step": (sum(pairs) / len(pairs)) if pairs else None,
        "halo_bytes_per_step": halo_bytes,
    }
    log(f"[bench] trace: planes {sorted(norm['lines'])}; modules "
        f"{sorted(ctx['modules'])}; kernels {sorted(ctx['kernels'])}; "
        f"sorts {sorted(sorts)} (binning only: {bool(ctx['sorts'])}); "
        f"collectives {len(ctx['collectives'])}; device events "
        f"{ {d: len(e) for d, e in norm['devices'].items()} }; host spans "
        f"{len(norm['host'])}; window {ctx['window']}")
    return ctx


def sorts_only_in_binning(sim, states) -> bool:
    """Every sort in the step's program is traced from core/grid.py (the
    binning), read from the jaxpr's source locations."""
    import jax

    if not states:
        return False
    prog = sim.engine.make_segment_runner(sim.mesh).programs[True]
    closed = jax.make_jaxpr(prog)(states[0], np.int32(1))
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "sort":
                frames = [f.file_name for f in
                          eqn.source_info.traceback.frames] \
                    if eqn.source_info.traceback else []
                found.append(any(f.endswith("repro/core/grid.py")
                                 for f in frames))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(closed.jaxpr)
    return bool(found) and all(found)


def emit(result: Dict[str, Any], out=sys.stdout) -> None:
    """The checks last on standard error, then the one JSON line last on
    standard output, with ``checks`` as its last key."""
    for k, c in result["checks"].items():
        log(f"[check] {k} = {c['value']!r} (limit {c['limit']!r})"
            f"{'' if c['ok'] else '  FAILED'}")
    line = {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device")}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in result["checks"].items()}
    print(json.dumps(line), file=out, flush=True)
