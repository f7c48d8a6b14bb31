"""The sampling deployment of the benchmark (`criteo1tb-share64-bagged`) at a
small size on the CPU: the bagged driver end to end against
`reference_bagged.py` with its control and planted faults, the reference's
streams against the program's, and the dispatch plan the deployment's
re-sort interval was chosen by.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (BENCH, os.path.join(BENCH, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# the bagged driver end to end, by import: tier-1 runs what
# `benchmark/tests/test_bagged_cell.py` runs by path
from test_bagged_cell import (  # noqa: E402,F401
    bagged_root, sound, test_float8_control_is_not_correct_bagged,
    test_planted_sampling_fault_is_not_correct,
    test_sound_bagged_run_is_correct_and_well_formed)
from test_scopes_bagged import (  # noqa: E402,F401
    test_every_reader_of_the_cell_finds_nothing_in_an_untraced_record,
    test_grouping_arithmetic, test_the_draw_is_read_with_its_stats_inside_host_inputs,
    test_the_flushes_carry_the_sampling_counters, traced_spans)
from harness import reference_bagged  # noqa: E402
import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.utils.mt19937 import Mt19937Random  # noqa: E402


# -- the reference's streams against the program's --------------------------
@pytest.mark.parametrize("seed,n,k", [(3, 100003, 80002), (2, 39, 31),
                                      (2 ** 32 - 1, 4099, 1)])
def test_reference_stream_is_the_programs(seed, n, k):
    """The reference derives upstream's stream from the seed alone; the
    program's replica draws the same doubles, and a bag the program draws
    passes the reference's check of the walk, twice running (ONE stream)."""
    program, stream = Mt19937Random(seed), reference_bagged.Stream(seed)
    assert np.array_equal(program.next_doubles(1000), stream.doubles(1000))
    for _ in range(2):
        bits = np.packbits(program.split_mask(n, k))
        assert reference_bagged.bag_gap(bits, n, k, stream) == 0.0
    if n < 100:
        assert np.array_equal(program.split_mask(n, k), stream.sample(n, k))


def test_a_wrong_bit_or_a_stale_bag_fails_the_walk():
    n, k = 100003, 80002
    bag = Mt19937Random(3).split_mask(n, k)
    flipped = bag.copy()
    flipped[[5, 77777]] = ~flipped[[5, 77777]]
    gap = reference_bagged.bag_gap(np.packbits(flipped), n, k,
                                   reference_bagged.Stream(3))
    # the flipped rows fail, and the rows between them whose draw lies
    # between the two probabilities the changed count gives
    assert 2 / n <= gap < 0.001
    # the first bag again where the stream has moved on to the second
    stream = reference_bagged.Stream(3)
    assert reference_bagged.bag_gap(np.packbits(bag), n, k, stream) == 0.0
    assert reference_bagged.bag_gap(np.packbits(bag), n, k, stream) > 0.2


# -- the dispatch plan -------------------------------------------------------
def _plan(reorder_every, bagging_freq, trees):
    """The dispatches `train_segment` makes over `trees` trees, the device
    work stubbed out: [(first tree, k, [kinds])] and the trees a bag was
    drawn before.  `iter_batch=8` is the chip's K (`auto` is 1 on the CPU),
    40,960 rows are enough for the compacted window to engage."""
    rng = np.random.RandomState(0)
    x = rng.randn(40960, 4).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 4, "verbose": -1,
              "device_type": "cpu", "hist_impl": "pallas", "iter_batch": 8,
              "hist_reorder_every": reorder_every, "bagging_fraction": 0.8,
              "bagging_freq": bagging_freq, "feature_fraction": 0.8}
    b = lgb.Booster(params, lgb.Dataset(
        x, label=(x[:, 0] > 0).astype(np.float32)))._gbdt
    log, draws = [], []

    def run_fused(bag, fmask, k_iters=1):
        if b._reorder_now():
            log.append("resort")
            b._trees_since_reorder, b._row_order = 0, "sorted"
        else:
            log.append("scan")
            b._trees_since_reorder += k_iters
        return []

    def arrange():
        log.append("arrange")
        b._row_order = "arranged"

    def bagging(it, cls):
        if it % bagging_freq == 0:
            draws.append(it)
            b._bag_arranged = False

    b._run_fused, b._arrange_for_bag, b._bagging = run_fused, arrange, bagging
    b._bag_mask_dev_fused = lambda cls: None
    assert b._bag_compact_rows() == 32768
    out, done = [], 0
    while done < trees:
        at = len(log)
        _, k = b.train_segment(trees - done, is_eval=False)
        out.append((done, k, log[at:]))
        done += k
    return out, draws


def _executables(plan):
    return {(kind, 0 if kind == "arrange" else k)
            for _, k, kinds in plan for kind in kinds}


def test_plan_at_15_and_5_is_one_period_of_four_executables():
    """`hist_reorder_every=15 bagging_freq=5`, the deployment's: a period of
    15 trees is the re-sort step (a draw and an arrangement before it),
    then K=4, K=5, K=5 with a draw and an arrangement before the last two,
    from the job's first tree on (the arrangement before it makes a row
    order, and the first tree re-sorts all the same)."""
    plan, draws = _plan(15, 5, 150)
    assert draws == list(range(0, 150, 5))
    period = [(1, ["arrange", "resort"]), (4, ["scan"]),
              (5, ["arrange", "scan"]), (5, ["arrange", "scan"])]
    assert [(k, kinds) for _, k, kinds in plan] == period * 10
    assert [t for t, _, kinds in plan if "resort" in kinds] == list(
        range(0, 150, 15))
    # the warm period runs every executable of the window
    assert _executables(plan[:4]) == _executables(plan) == {
        ("arrange", 0), ("resort", 1), ("scan", 4), ("scan", 5)}


def test_plan_at_the_default_16_and_5_repeats_every_80_trees():
    """At the default re-sort interval the cadence and the bagging epoch
    drift against each other: the plan repeats only every 80 trees and
    runs SEVEN executables, which no warm period covers."""
    plan, _ = _plan(16, 5, 240)
    sizes = [k for _, k, _ in plan]
    pattern = [1, 4, 5, 5, 1, 1, 3, 5, 5, 2, 1, 2, 5, 5, 3, 1, 1, 5, 5, 4,
               1, 5, 5, 5]
    assert sum(pattern) == 80
    assert sizes == pattern * 3
    resorts = [t for t, _, kinds in plan if "resort" in kinds]
    assert resorts == list(range(0, 240, 16))
    assert _executables(plan) == {("arrange", 0), ("resort", 1)} | {
        ("scan", k) for k in (1, 2, 3, 4, 5)}
    assert _executables(plan[:5]) < _executables(plan)
