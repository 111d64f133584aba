"""The program names its layers: every ``sim.*`` scope reaches the
compiled segment programs' HLO metadata, on one device and on a 2x2 mesh
of virtual devices, and ``layers.names_by_scope`` gives each instruction
its innermost scope."""

import json
import os
import subprocess
import sys
import textwrap

from benchlib import hlo, layers, run, spec, traffic
from conftest import BENCH, ROOT

LAYERS = {"sim.guards", "sim.aura", "sim.sweep", "sim.update",
          "sim.binning", "sim.migration", "sim.carry"}


def test_innermost_scope_of_an_op_path():
    path = "jit(segment_full)/sim.carry/while/body/sim.migration/" \
           "sim.binning/jit(sort)/sort"
    assert layers.innermost_scope(path) == "sim.binning"
    assert layers.innermost_scope("jit(simulate)/while/body/add") is None


def test_names_by_scope_maps_a_nested_op_to_its_innermost_scope():
    body = "jit(segment_delta)/sim.carry/while/body"
    text = "\n".join([
        "HloModule jit_segment_delta, is_scheduled=true",
        "ENTRY %main.1 (p: s32[8]) -> s32[8] {",
        f'  %sort.1 = s32[8]{{0}} sort(%p), dimensions={{0}}, metadata='
        f'{{op_name="{body}/sim.migration/sim.binning/jit(sort)/sort"}}',
        f'  %fusion.2 = s32[8]{{0}} fusion(%sort.1), kind=kLoop, '
        f'calls=%c, metadata={{op_name="{body}/sim.migration/concatenate"}}',
        "  %copy.3 = s32[8]{0} copy(%fusion.2)",
        f'  ROOT %fusion.4 = s32[8]{{0}} fusion(%copy.3), kind=kLoop, '
        f'calls=%d, metadata={{op_name="{body}/sim.sweep/sim.sweep.faces/'
        f'dynamic_update_slice"}}',
        "}"])
    assert hlo.module_name(text) == "jit_segment_delta"
    assert layers.names_by_scope(text) == {
        "sim.binning": {"sort.1"}, "sim.migration": {"fusion.2"},
        "sim.sweep.faces": {"fusion.4"}}


def test_the_one_chip_segment_program_names_every_layer(small_root,
                                                        fresh_programs):
    """The cell's own program, built as the harness builds it (guards
    on), compiled for this device."""
    cell = spec.load_cell("clustering.dense-512", small_root)
    sim = run.build_sim(cell)
    pos, attrs = traffic.draw_agents(cell.config, cell.traffic, 20260011)
    sim.init(pos, attrs, seed=0)
    (exe,) = run.programs_run(sim, sim.state, 1).values()
    text = exe.as_text()
    assert hlo.module_name(text) == "jit_segment_full"
    assert set(layers.names_by_scope(text)) == LAYERS


TWO_BY_TWO = """
import json, sys
sys.path[:0] = [{tests!r}, {bench!r}, {src!r}]
from conftest import make_root
from repro.core import compile_cache
compile_cache.enable_persistent_cache = lambda: "off"
from benchlib import hlo, layers, run, spec, traffic

cell = spec.load_cell("clustering.2x2-512", make_root({root!r}, cells=16))
sim = run.build_sim(cell)
pos, attrs = traffic.draw_agents(cell.config, cell.traffic, 20260012)
sim.init(pos, attrs, seed=0)
out = {{}}
for exe in run.programs_run(sim, sim.state, 1).values():
    text = exe.as_text()
    out[hlo.module_name(text)] = sorted(layers.names_by_scope(text))
print(json.dumps(out))
"""


def test_the_2x2_segment_programs_name_every_layer(tmp_path):
    """Both programs of the 2x2 cell (full refresh and int8 delta, the
    sweep split around the exchange) on four virtual devices, under
    module names that tell them apart in a trace."""
    code = TWO_BY_TWO.format(
        tests=os.path.join(BENCH, "tests"), bench=BENCH,
        src=os.path.join(ROOT, "src"), root=str(tmp_path / "root"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    split = (LAYERS - {"sim.sweep"}) | {"sim.sweep.interior",
                                        "sim.sweep.faces"}
    assert got == {"jit_segment_full": sorted(split),
                   "jit_segment_delta": sorted(split)}
