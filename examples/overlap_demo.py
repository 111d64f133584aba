"""Communication-budget smoke (docs/performance.md §4): the three layers
that keep the wire off the critical path, end-to-end on a 2x2 host-device
mesh.

* **Overlap** — ``overlap="on"`` splits every sweep into an interior pass
  (scheduled concurrently with the ``ppermute`` aura exchange) and a
  boundary pass that consumes the received ring; results are pinned
  bit-exact vs the sequential sweep, so this demo just runs it hot.
* **Delta by default** — ``make_sim`` resolves multi-device sims to the
  int8 delta-encoded aura exchange (paper §2.3).
* **Device-to-device re-shard** — a skewed two-cluster density triggers
  one mid-run rebalance onto an uneven RCB partition, migrated by the
  device-to-device fast path (``transport="device"``) with a deferred
  (async-snapshot) plan: zero bytes through the host, asserted by
  trapping ``flatten_state``.

    PYTHONPATH=src python examples/overlap_demo.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import numpy as np

import repro.core.reshard as reshard_mod
from repro.core import Rebalance
from repro.core.reshard import current_imbalance
from repro.sims import cell_clustering
from repro.sims.common import make_sim


def main():
    sim = make_sim(
        cell_clustering.behavior(adhesion=0.3),
        interior=(8, 8), mesh_shape=(2, 2), cap=64, dt=0.1,
        overlap="on",
        rebalance=Rebalance(every=6, threshold=0.3, ownership="rcb",
                            transport="device", defer=True))
    assert sim.engine.delta_cfg.enabled, "multi-device sims default to delta"
    print(f"aura exchange: int8 delta, refresh_interval="
          f"{sim.engine.delta_cfg.refresh_interval}; overlap=on")

    # two diagonal Gaussian clusters: half the devices own almost nothing
    rng = np.random.default_rng(0)
    n = 600
    centers = np.asarray([(8.0, 8.0), (24.0, 24.0)])
    pos = centers[rng.integers(0, 2, n)] + rng.normal(0, 3.0, (n, 2))
    pos = np.clip(pos, 0.5, 31.5).astype(np.float32)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}
    sim.init(pos, attrs, seed=0)
    print(f"static 2x2 split: imbalance = "
          f"{current_imbalance(sim.geom, sim.state):.2f}")

    # any call into the host-path flattener during the run is a regression
    calls = []
    orig = reshard_mod.flatten_state

    def trap(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    reshard_mod.flatten_state = trap
    try:
        sim.run(20)
    finally:
        reshard_mod.flatten_state = orig

    applied = [r for r in sim.rebalancer.history if r["applied"]]
    assert applied, sim.rebalancer.history
    for rec in applied:
        assert rec["transport"] == "device", rec
        assert rec.get("deferred"), rec
        print(f"it {rec['it']}: deferred device-to-device re-shard "
              f"{rec['mesh_from']} -> {rec['mesh_to']}  imbalance "
              f"{rec['imbalance_before']:.2f} -> "
              f"{rec['imbalance_after']:.2f}  "
              f"(migration {rec['migration_s']*1e3:.0f} ms)")
    assert not calls, "device re-shard must not touch flatten_state"
    assert sim.engine.geom.uneven, "rcb re-shard should land uneven"

    dropped = int(np.asarray(sim.state.dropped).sum())
    assert sim.n_agents() + dropped == n, (sim.n_agents(), dropped)
    print(f"final mesh {sim.engine.geom.mesh_shape} (uneven rcb), "
          f"imbalance = {current_imbalance(sim.geom, sim.state):.2f}, "
          f"agents {sim.n_agents()}/{n} (drops: {dropped}), "
          f"zero host bytes moved")


if __name__ == "__main__":
    main()
