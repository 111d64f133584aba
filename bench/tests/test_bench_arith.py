"""The arithmetic behind the end-to-end metrics, the pair count behind
sweep_pairs_per_s, and the comparison, on fixed inputs."""

import collections
import itertools

import numpy as np
import pytest

from benchlib import cells, compare, run

Stats = collections.namedtuple(
    "Stats", "argument_size_in_bytes output_size_in_bytes "
             "alias_size_in_bytes temp_size_in_bytes "
             "generated_code_size_in_bytes")


def test_agent_updates_per_s_is_all_agent_steps_over_the_window():
    # 3 calls of 1 step at 1,000 live agents, one call at 990, in 2.5 s
    assert run.updates_per_s(3 * 1000 + 990, 2.5) == pytest.approx(1596.0)


def test_hbm_bytes_per_agent_takes_the_largest_program_and_fullest_ratio():
    full = Stats(100, 100, 40, 1000, 60)      # 1220 B on every chip
    delta = Stats(100, 100, 40, 1200, 60)     # 1420 B: the larger program
    assert run.hbm_bytes_per_agent([full], [10]) == pytest.approx(122.0)
    # SPMD: one program, the chip that owns fewest agents reads highest
    assert run.hbm_bytes_per_agent([full, delta], [20, 10, 40, 50]) \
        == pytest.approx(142.0)


def _brute_pairs(pos, cell_size, grid, toroidal):
    c = np.clip(np.floor(pos / cell_size).astype(int), 0,
                np.asarray(grid) - 1)
    n = 0
    for i, j in itertools.permutations(range(len(pos)), 2):
        d = np.abs(c[i] - c[j])
        if toroidal:
            d = np.minimum(d, np.asarray(grid) - d)
        n += bool((d <= 1).all())
    return n


@pytest.mark.parametrize("grid", [(5, 4), (5, 4, 3)])
@pytest.mark.parametrize("toroidal", [False, True])
def test_pair_count_counts_ordered_pairs_in_adjacent_cells(toroidal, grid):
    rng = np.random.default_rng(3)
    size = [2.0 * g for g in grid]
    pos = rng.uniform(0, size, size=(40, len(grid))).astype(np.float32)
    assert cells.pair_count(pos, 2.0, grid, toroidal) == \
        _brute_pairs(pos, 2.0, grid, toroidal)


class _State:
    def __init__(self, valid):
        self.soa = collections.namedtuple("Soa", "valid")(valid)


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2), (2, 1, 2)])
def test_agents_per_chip_counts_each_block_of_the_slot_grid(mesh_shape):
    rng = np.random.default_rng(5)
    shape = tuple(4 * m for m in mesh_shape) + (3,)
    valid = rng.random(shape) < 0.5
    blocks = list(itertools.product(*(range(m) for m in mesh_shape)))
    want = [int(valid[tuple(slice(4 * b, 4 * b + 4) for b in blk)].sum())
            for blk in blocks]
    assert run.agents_per_chip(_State(valid), mesh_shape) == want


def test_pair_count_of_one_full_cell():
    pos = np.full((5, 2), 1.0, np.float32)
    assert cells.pair_count(pos, 2.0, (3, 3), False) == 20


def _answer(ids, pos):
    return {"ids": np.asarray(ids, np.int64),
            "pos": np.asarray(pos, np.float32)}


def test_compare_counts_missing_duplicated_and_moved_agents():
    ref = {"pos": np.asarray([[1.0, 1.0], [2.0, 2.0], [3.0, 3.0],
                              [4.0, 4.0]], np.float32)}
    same = compare.compare(_answer([0, 1, 2, 3], ref["pos"]), ref,
                           (8.0, 8.0), (False, False))
    assert same == {"agents_missing": 0.0, "pos_mismatch_share": 0.0}
    one_ulp = ref["pos"].copy()
    one_ulp[0, 0] = np.nextafter(one_ulp[0, 0], np.float32(9))
    assert compare.compare(_answer([0, 1, 2, 3], one_ulp), ref, (8.0, 8.0),
                           (False, False))["pos_mismatch_share"] == 0.0
    moved = ref["pos"].copy()
    moved[1] += 1e-3
    got = compare.compare(_answer([0, 1, 2, 3, 3], np.concatenate(
        [moved, moved[3:]])), ref, (8.0, 8.0), (False, False))
    assert got["agents_missing"] == 1.0           # id 3 twice
    assert got["pos_mismatch_share"] == 0.25
    lost = compare.compare(_answer([0, 2, 3], ref["pos"][[0, 2, 3]]), ref,
                           (8.0, 8.0), (False, False))
    assert lost["agents_missing"] == 1.0


def test_compare_takes_the_minimum_image_on_a_torus():
    ref = {"pos": np.asarray([[0.0, 1.0]], np.float32)}
    wrapped = _answer([0], [[8.0, 1.0]])          # the same point at x = L
    assert compare.compare(wrapped, ref, (8.0, 8.0), (True, True))[
        "pos_mismatch_share"] == 0.0
    assert compare.compare(wrapped, ref, (8.0, 8.0), (False, False))[
        "pos_mismatch_share"] == 1.0


def test_judge_puts_each_number_beside_its_limit():
    got = compare.judge({"a": 0.0, "b": 0.2, "c": 5.0},
                        {"a": 0, "b": 0.1})
    assert got == {"a": {"value": 0.0, "limit": 0.0, "ok": True},
                   "b": {"value": 0.2, "limit": 0.1, "ok": False}}
    with pytest.raises(KeyError):
        compare.judge({"a": 0.0}, {"a": 0, "z": 1})


def test_summary_gives_the_worst_step_and_the_first_step_alone():
    steps = [{"agents_missing": 0.0, "pos_mismatch_share": 0.0},
             {"agents_missing": 1.0, "pos_mismatch_share": 0.25}]
    assert compare.summary(steps) == {"agents_missing": 1.0,
                                      "pos_mismatch_share": 0.25,
                                      "first_step_mismatch_share": 0.0}
