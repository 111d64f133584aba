"""The comparison that decides ``correct``: the program's answers against
the plain reference's, agent by agent, matched by global id.

Numbers compared (each against its limit in ``bench/limits/<cell>.json``):

* ``agents_missing``: reference agents absent from the program, plus
  program agents the reference lacks, plus duplicated ids.  Exact.
* ``pos_mismatch_share``: share of matched agents whose position differs
  from the reference's, on some axis (minimum image on toroidal axes), by
  more than ``t`` float32 ulps after ``t`` steps, an ulp taken at the
  largest magnitude the agent's position or displacement had so far.  A
  step's update ``p + dp`` rounds once or, fused, once less, and forces
  summed in another order move ``dp`` by far less than an ulp of ``p``;
  so two correct float32 programs stay within an ulp a step for nearly
  every agent, while an error in the pair forces, the update, the binning
  or the exchange moves many agents further.
* ``first_step_mismatch_share``: ``pos_mismatch_share`` after the first
  step of a span alone.  The program takes that step with a full float32
  aura exchange (``Simulation.with_state`` forces a full refresh), so a
  lossy delta codec has not acted yet: a mesh of chips matches the
  reference to the ulp there as one chip does, and an exchange left out
  or broken shows on every agent next to a seam, however few of the
  agents those are.
* ``<attr>_mismatch_share`` for each integer attribute the configuration
  lists under ``compare_attrs``: share of matched agents whose value
  differs.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def answer_of(state, ndim: int, attrs: Sequence[str] = ()) -> Dict:
    """Host copy of one program state: every live agent's id, position,
    the listed attributes and the slot it occupies (a multi-index into the
    global ``(*cells, K)`` slot grid), sorted by id.  The by-id matching is
    ``chip_smoke.py``'s."""
    v = np.asarray(state.soa.valid)
    flat = np.flatnonzero(v.ravel())
    rank = np.asarray(state.soa.attrs["gid_rank"]).ravel()[flat]
    count = np.asarray(state.soa.attrs["gid_count"]).ravel()[flat]
    ids = (rank.astype(np.int64) << 32) | count.astype(np.int64)
    order = np.argsort(ids, kind="stable")
    flat = flat[order]
    out = {
        "ids": ids[order],
        "pos": np.asarray(state.soa.attrs["pos"]).reshape(-1, ndim)[flat],
        "slot": np.stack(np.unravel_index(flat, v.shape), axis=1),
    }
    for a in attrs:
        out[a] = np.asarray(state.soa.attrs[a]).ravel()[flat]
    return out


def magnitudes(pos0: np.ndarray, ref: Sequence[Dict]) -> List[np.ndarray]:
    """Per step of the reference, each agent's largest position or
    displacement component so far: the scale of its ulp."""
    out, mag, prev = [], np.abs(pos0).max(axis=1), pos0
    for r in ref:
        mag = np.maximum(mag, np.maximum(np.abs(r["pos"]).max(axis=1),
                                         np.abs(r["pos"] - prev).max(axis=1)))
        prev = r["pos"]
        out.append(mag)
    return out


def compare(prog: Dict, ref: Dict, domain: Sequence[float],
            toroidal: Sequence[bool], attrs: Sequence[str] = (),
            steps: int = 1, mag: np.ndarray = None) -> Dict[str, float]:
    """``prog`` from :func:`answer_of`; ``ref`` holds ``pos`` and the
    attributes for agents ``0..N-1`` (id = index) after ``steps`` steps,
    ``mag`` the scale of each agent's ulp (:func:`magnitudes`)."""
    n = len(ref["pos"])
    ids = prog["ids"]
    ok = (ids >= 0) & (ids < n)
    uniq, first = np.unique(ids[ok], return_index=True)
    missing = (n - len(uniq)) + int((~ok).sum()) + (int(ok.sum()) - len(uniq))
    sel = np.flatnonzero(ok)[first]
    p = prog["pos"][sel].astype(np.float64)
    r = ref["pos"][uniq].astype(np.float64)
    d = p - r
    for a, (tor, size) in enumerate(zip(toroidal, domain)):
        if tor:
            d[:, a] -= size * np.round(d[:, a] / size)
    scale = np.abs(r).max(axis=1) if mag is None else mag[uniq]
    tol = steps * np.spacing(scale.astype(np.float32)).astype(np.float64)
    off = (np.abs(d) > tol[:, None]).any(axis=1)
    out = {"agents_missing": float(missing),
           "pos_mismatch_share": float(off.mean()) if len(off) else 1.0}
    for a in attrs:
        diff = prog[a][sel] != ref[a][uniq]
        out[f"{a}_mismatch_share"] = float(diff.mean()) if len(diff) else 1.0
    return out


def summary(readings: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """Per number, the largest reading over the compared steps, and
    ``first_step_mismatch_share``: ``pos_mismatch_share`` after the first
    step alone."""
    keys = readings[0].keys()
    out = {k: max(r[k] for r in readings) for k in keys}
    out["first_step_mismatch_share"] = readings[0]["pos_mismatch_share"]
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, float]]:
    """Each number beside its limit; a number passes at or below it."""
    missing = set(limits) - set(numbers)
    if missing:
        raise KeyError(f"limits for numbers never computed: {missing}")
    return {k: {"value": numbers[k], "limit": float(limits[k]),
                "ok": bool(numbers[k] <= float(limits[k]))}
            for k in limits}
