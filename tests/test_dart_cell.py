"""The dropout-boosting deployment of the benchmark (`criteo1tb-share64-dart`)
at a small size on the CPU: the DART driver end to end against
`reference_dart.py` with its control and planted faults, through a bank that
holds every tree and through one that replays, and the reference's drop lists
against the program's.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (BENCH, os.path.join(BENCH, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# the DART driver end to end, by import: tier-1 runs what
# `benchmark/tests/test_dart_cell.py` runs by path (by its path here too:
# the two files share a name)
import importlib.util  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "bench_test_dart_cell", os.path.join(BENCH, "tests", "test_dart_cell.py"))
_cell = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cell)
dart_root, sound_dart = _cell.dart_root, _cell.sound_dart
test_a_bank_that_replays_is_correct_too = (
    _cell.test_a_bank_that_replays_is_correct_too)
test_every_reader_of_the_cell_finds_nothing_in_an_untraced_record = (
    _cell.test_every_reader_of_the_cell_finds_nothing_in_an_untraced_record)
test_float8_control_is_not_correct_dart = (
    _cell.test_float8_control_is_not_correct_dart)
test_planted_dart_fault_is_not_correct = (
    _cell.test_planted_dart_fault_is_not_correct)
test_sound_dart_run_is_correct_and_well_formed = (
    _cell.test_sound_dart_run_is_correct_and_well_formed)
test_weights_follow_the_lists = _cell.test_weights_follow_the_lists
from test_scopes_dart import (  # noqa: E402,F401
    test_dart_grouping_arithmetic,
    test_the_lottery_is_read_with_its_stats_inside_host_inputs, traced_dart)
from harness import reference_dart  # noqa: E402
import lightgbm_tpu as lgb  # noqa: E402
import numpy as np  # noqa: E402


@pytest.mark.parametrize("rate,seed", [(0.1, 4), (0.3, 4), (0.01, 7),
                                       (0.7, 2 ** 31 + 3)])
def test_reference_drop_lists_are_the_programs(rate, seed):
    """The reference derives the drop lists from `drop_seed` alone; the
    program's lottery, run over 48 iterations without a device, draws the
    same lists (one tree forced where the lottery drops none)."""
    rng = np.random.RandomState(0)
    x = rng.randn(2000, 3).astype(np.float32)
    b = lgb.Booster({"objective": "binary", "boosting_type": "dart",
                     "drop_rate": rate, "drop_seed": seed, "verbose": -1,
                     "device_type": "cpu"},
                    lgb.Dataset(x, label=(x[:, 0] > 0).astype(np.float32)))
    for it in range(48):
        b._gbdt._draw_drops(it)
    want = reference_dart.drop_lists({"drop_rate": rate, "drop_seed": seed},
                                     48)
    assert b._gbdt.drop_history() == want
    assert want[0] == [] and all(len(d) >= 1 for d in want[1:])
