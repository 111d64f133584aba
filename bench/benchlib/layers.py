"""The program's own layer names in a trace: the ``sim.*`` device scopes
of its compiled step and the ``sim.*`` host spans of ``Simulation.run``.

The program wraps each layer of its step in a ``jax.named_scope``
(``sim.binning``, ``sim.sweep``, ...), which reaches every instruction's
``op_name`` metadata as one segment of its path
(``jit(f)/while/body/sim.binning/jit(sort)/sort``); a fusion carries its
root op's metadata.  An instruction belongs to the innermost ``sim.*``
segment of its path; a program without such scopes has none.  Its host
spans (``sim.dispatch``, ``sim.wait``, ``sim.guards.host_check``, ...)
are ``jax.profiler.TraceAnnotation``s on the profiler's clock, beside the
harness's ``bench.*`` spans.

The benchmark's own reduction (``trace.normalize``) keeps neither; this
module reads them from the same trace for ``bench/tools/layer_times.py``,
in the normalized form of ``trace.py`` with two additions: the host spans
named ``sim.*`` are kept, and where the ``hlo_module`` stat is empty (as
on the v5e) an op takes the module of the event that encloses it on its
plane's ``XLA Modules`` line, so that two programs that share an
instruction name are told apart.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from benchlib import trace as tr
from benchlib.hlo import INSTR

SCOPE_PREFIX = "sim."
HOST_PREFIXES = ("bench.", SCOPE_PREFIX)
MODULES_LINE = "XLA Modules"
OP_NAME = re.compile(r'op_name="([^"]*)"')

# metric -> the scope whose ops (and those of scopes nested under its name)
# it times; None times the ops in no scope
DEVICE_LAYERS = {
    "sweep_ms_per_step": "sim.sweep",
    "binning_ms_per_step": "sim.binning",
    "aura_ms_per_step": "sim.aura",
    "update_ms_per_step": "sim.update",
    "migration_ms_per_step": "sim.migration",
    "guard_device_ms_per_step": "sim.guards",
    "unscoped_device_ms_per_step": None,
}
# metric -> the host span whose durations it sums
HOST_LAYERS = {"guard_host_ms_per_step": "sim.guards.host_check"}

ScopeMap = Dict[str, Dict[str, Set[str]]]


def innermost_scope(op_name: str) -> Optional[str]:
    """The last ``sim.*`` segment of an ``op_name`` path, or None."""
    for seg in reversed(op_name.split("/")):
        if seg.startswith(SCOPE_PREFIX):
            return seg
    return None


def names_by_scope(text: str) -> Dict[str, Set[str]]:
    """Scope -> the instructions of HLO ``text`` whose innermost ``sim.*``
    scope it is."""
    out: Dict[str, Set[str]] = {}
    for line in text.splitlines():
        m = INSTR.match(line)
        o = OP_NAME.search(line) if m else None
        scope = innermost_scope(o.group(1)) if o else None
        if scope:
            out.setdefault(scope, set()).add(m.group(1))
    return out


def enclosing(spans: List[Tuple[float, float, str]], t: float) -> str:
    """Name of the span of sorted, disjoint ``spans`` that holds ``t``,
    or ''."""
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    return spans[i][2] if i >= 0 and t < spans[i][1] else ""


def normalize(trace_dir: str, device_ids: Set[int]) -> Dict:
    """``trace.normalize`` that also keeps the program's ``sim.*`` host
    spans and gives each device op its module on the v5e."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no trace under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, List] = {}
    host: List = []
    lines_seen: Dict[str, List[str]] = {}
    for plane in data.planes:
        lines_seen[plane.name] = sorted({ln.name for ln in plane.lines})
        dev = plane.name.rsplit(":", 1)[-1]
        if plane.name.startswith("/device:TPU:") and dev.isdigit() \
                and int(dev) in device_ids:
            modules = sorted(
                (float(ev.start_ns), float(ev.end_ns), ev.name)
                for line in plane.lines if line.name == MODULES_LINE
                for ev in line.events)
            evs = []
            for line in plane.lines:
                if line.name != tr.OPS_LINE:
                    continue
                for ev in line.events:
                    name, opcode = tr.op_name(ev.name)
                    if opcode in tr.CONTAINERS:
                        continue
                    start = float(ev.start_ns)
                    module = str(dict(ev.stats).get("hlo_module", "")) \
                        or enclosing(modules, start)
                    evs.append([name, start, float(ev.end_ns), module])
            devices[dev] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.end_ns)])
    return {"devices": devices, "host": host, "lines": lines_seen}


def _scope_of(scopes: ScopeMap):
    """(instruction, trace module) -> the instruction's innermost scope in
    the program that ran it.  An op of a module ``scopes`` does not hold,
    or of no known module where several could hold it, has none."""
    by_module = {mod: {n: sc for sc, names in sm.items() for n in names}
                 for mod, sm in scopes.items()}

    def lookup(name: str, m: str) -> Optional[str]:
        hit = [t for mod, t in by_module.items()
               if (tr.in_module(m, mod) if m else len(by_module) == 1)]
        return hit[0].get(name) if len(hit) == 1 else None

    return lookup


def scoped_ns(norm: Dict, w: tr.Interval, scopes: ScopeMap,
              layer: Optional[str]) -> Optional[float]:
    """Device time (ns) in the window of the ops whose innermost scope is
    ``layer`` or nested under its name (``sim.sweep`` takes
    ``sim.sweep.faces``), mean over chips; ``layer=None`` takes the ops in
    no ``sim.*`` scope.  None when no such op ran, or when the program
    names no scope at all."""
    if not any(scopes.values()):
        return None
    scope_of = _scope_of(scopes)

    def picked(n: str, m: str) -> bool:
        sc = scope_of(n, m)
        if layer is None:
            return sc is None
        return sc is not None and (sc == layer or
                                   sc.startswith(layer + "."))

    devs = sorted(norm["devices"])
    per = [sum(e - s for s, e in tr.clip(
        [(s, e) for n, s, e, m in norm["devices"][d] if picked(n, m)], w))
        for d in devs]
    if not devs or not any(per):
        return None
    return sum(per) / len(devs)


def host_ns(norm: Dict, w: tr.Interval, name: str) -> Optional[float]:
    """Summed duration (ns) in the window of the host spans ``name``;
    None when there is none."""
    iv = tr.clip([(s, e) for n, s, e in norm["host"] if n == name], w)
    return sum(e - s for s, e in iv) if iv else None


def readings(norm: Dict, scopes: ScopeMap, steps: int) -> Dict[str, float]:
    """Each layer's ms per simulated step in the traced window (device
    time the mean over chips); a layer with nothing to read is left
    out."""
    w = tr.window(norm)
    if not w or not steps:
        return {}
    ns = {m: scoped_ns(norm, w, scopes, sc) for m, sc in DEVICE_LAYERS.items()}
    ns.update({m: host_ns(norm, w, sp) for m, sp in HOST_LAYERS.items()})
    return {m: v / 1e6 / steps for m, v in ns.items() if v is not None}


def idle_gaps(norm: Dict, w: tr.Interval, top: int = 10) -> List[List]:
    """``trace.idle_gaps``, each gap named by the innermost (shortest)
    host span that covers more than half of it, else by the span that
    overlaps it most (``host.other`` if none).  Spans that do not nest,
    as the harness's own, name a gap as ``trace.idle_gaps`` does."""
    def name_of(gs: float, ge: float) -> str:
        best, over = "host.other", 0.0
        inner = []
        for n, s, e in norm["host"]:
            o = min(e, ge) - max(s, gs)
            if o > over:
                best, over = n, o
            if o > (ge - gs) / 2:
                inner.append((e - s, n))
        return min(inner)[1] if inner else best

    gaps = []
    for d in sorted(norm["devices"]):
        busy = tr.merge(tr.clip(
            [(s, e) for _, s, e, _ in norm["devices"][d]], w))
        for gs, ge in tr.minus([w], busy):
            gaps.append([name_of(gs, ge), (ge - gs) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    return gaps[:top]
