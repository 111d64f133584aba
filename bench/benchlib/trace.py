"""From a profiler trace to the numbers the per-layer metrics read.

``normalize`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps two things, on the profiler's one clock:

* ``devices``: per chip, the device's operations, ``[name, start_ns,
  end_ns, module]``, from the ``XLA Ops`` line of each ``/device:TPU:n``
  plane.  On a v5e an event is named by its HLO instruction's text
  (``%sort.62 = (s32[...]) sort(...), ...``); ``name`` is the
  instruction's name (``sort.62``), which the compiled program's HLO
  gives too, and ``module`` the ``hlo_module`` stat where there is one.
  Control-flow ops (``while``, ``conditional``, ``call``) are left out:
  their events enclose the ops they run, which are recorded themselves;
* ``host``: the harness's own spans (``bench.call``, ``bench.reset``,
  ``bench.readback``), ``[name, start_ns, end_ns]``.

Everything else here works on that normalized form, which is what the
tests feed it from a small recorded trace.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from benchlib.hlo import INSTR

Interval = Tuple[float, float]

HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
CONTAINERS = frozenset({"while", "conditional", "call"})


def op_name(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of a device event's name; a bare name
    is its own instruction name, with no opcode."""
    m = INSTR.match(text)
    return (m.group(1), m.group(2)) if m else (text, "")


def normalize(trace_dir: str, device_ids: Set[int]) -> Dict:
    """Read the trace, keeping the device planes of ``device_ids`` (the
    chips the cell runs on) and the harness's host spans."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, List] = {}
    host: List = []
    lines_seen = {}
    for plane in data.planes:
        lines_seen[plane.name] = [ln.name for ln in plane.lines]
        dev = plane.name[len("/device:TPU:"):]
        if plane.name.startswith("/device:TPU:") and dev.isdigit() \
                and int(dev) in device_ids:
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name, opcode = op_name(ev.name)
                    if opcode in CONTAINERS:
                        continue
                    stats = dict(ev.stats)
                    evs.append([name, float(ev.start_ns), float(ev.end_ns),
                                str(stats.get("hlo_module", ""))])
            devices[dev] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.end_ns)])
    return {"devices": devices, "host": host, "lines": lines_seen}


def merge(iv: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(iv: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge(iv))


def clip(iv: Iterable[Interval], w: Interval) -> List[Interval]:
    return [(max(s, w[0]), min(e, w[1])) for s, e in iv
            if min(e, w[1]) > max(s, w[0])]


def minus(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window(norm: Dict) -> Optional[Interval]:
    """From the start of the first ``bench.call`` to the end of the last."""
    calls = [(s, e) for n, s, e in norm["host"] if n == "bench.call"]
    if not calls:
        return None
    return (min(s for s, _ in calls), max(e for _, e in calls))


def in_module(m: str, module: Optional[str]) -> bool:
    """An op belongs to ``module`` when either is unknown, or when its
    module stat names it (a trace may add a suffix such as ``(3)``)."""
    return not module or not m or m == module or \
        m.startswith(module + "(") or m.startswith(module + ".")


def _ops(norm: Dict, dev: str, w: Interval,
         names: Optional[Set[str]] = None,
         module: Optional[str] = None) -> List[Interval]:
    return clip([(s, e) for n, s, e, m in norm["devices"][dev]
                 if (names is None or n in names) and in_module(m, module)],
                w)


def busy_ns(norm: Dict, w: Interval) -> float:
    """Union of device-op intervals in the window, mean over chips."""
    devs = sorted(norm["devices"])
    if not devs:
        return 0.0
    return sum(measure(_ops(norm, d, w)) for d in devs) / len(devs)


def time_in(norm: Dict, w: Interval, names: Set[str],
            module: Optional[str] = None) -> Optional[float]:
    """Device time (ns) of the named ops in the window, mean over chips;
    None when no such op ran."""
    devs = sorted(norm["devices"])
    per = [sum(e - s for s, e in _ops(norm, d, w, names, module))
           for d in devs]
    if not devs or not any(per):
        return None
    return sum(per) / len(devs)


def exposed_ns(norm: Dict, w: Interval, names: Set[str],
               module: Optional[str] = None) -> Optional[float]:
    """Device time of the named ops during which that chip runs no other
    op, mean over chips; None when no such op ran."""
    devs = sorted(norm["devices"])
    total, seen = 0.0, False
    for d in devs:
        own = merge(_ops(norm, d, w, names, module))
        seen = seen or bool(own)
        rest = merge((s, e) for n, s, e, m in norm["devices"][d]
                     if n not in names or not in_module(m, module))
        total += measure(minus(own, merge(clip(rest, w))))
    return total / len(devs) if seen else None


def op_totals(norm: Dict, w: Interval, top: int = 10) -> List[List]:
    """The device ops that took most time, seconds per chip."""
    devs = sorted(norm["devices"])
    tot: Dict[str, float] = {}
    for d in devs:
        for n, s, e, _ in norm["devices"][d]:
            for cs, ce in clip([(s, e)], w):
                tot[n] = tot.get(n, 0.0) + (ce - cs)
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[n, t / len(devs) / 1e9] for n, t in rows]


def idle_gaps(norm: Dict, w: Interval, top: int = 10) -> List[List]:
    """The longest stretches in which a chip ran nothing, each named by
    the harness span that overlaps it most (``host.other`` if none)."""
    def name_of(gs: float, ge: float) -> str:
        best, over = "host.other", 0.0
        for n, s, e in norm["host"]:
            o = min(e, ge) - max(s, gs)
            if o > over:
                best, over = n, o
        return best

    gaps = []
    for d in sorted(norm["devices"]):
        for gs, ge in minus([w], merge(_ops(norm, d, w))):
            gaps.append([name_of(gs, ge), (ge - gs) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    return gaps[:top]
