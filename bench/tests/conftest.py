"""Shared set-up of the benchmark's own tests: ``benchlib`` and the
program on the path, and a scratch checkout whose mixes are shrunk to a
size a CPU test can run."""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def make_root(dest: str, cells: int = 16, **mix_overrides) -> str:
    """A checkout at ``dest`` with this repository's BENCHMARK.json and
    bench/, every mix cut to ``cells`` x ``cells`` global cells, and the
    program linked in."""
    shutil.copytree(BENCH, os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(dest, "src"))
    tdir = os.path.join(dest, "bench", "traffic")
    for f in os.listdir(tdir):
        with open(os.path.join(tdir, f)) as fh:
            mix = json.load(fh)
        mix["interior"] = [cells // m for m in mix["mesh_shape"]]
        mix.update(mix_overrides)
        with open(os.path.join(tdir, f), "w") as fh:
            json.dump(mix, fh)
    return dest


@pytest.fixture
def small_root(tmp_path):
    return make_root(str(tmp_path))


ENGINE_CACHES = ("_cached_segment_runner", "_cached_local_step",
                 "_cached_sharded_step")


@pytest.fixture
def fresh_programs(monkeypatch):
    """No compiled step survives from one test to the next, and the
    persistent compile cache stays off in the test process."""
    from repro.core import compile_cache, engine

    monkeypatch.setattr(compile_cache, "enable_persistent_cache",
                        lambda: "off")

    def clear():
        for name in ENGINE_CACHES:
            getattr(engine, name).cache_clear()

    clear()
    yield engine
    clear()
