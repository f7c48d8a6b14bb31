"""The ranking deployment of the benchmark (`istella-letor-lambdarank`) at a
small size on the CPU: the plain reference's lambdas against the program's,
the ranked driver end to end with its control and planted faults, the
ranked row generator, and what this deployment added to the program (the
pair counters on `lgbm.flush`, `row_slot` among the words a re-sort
carries).
"""

import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (BENCH, os.path.join(BENCH, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import ranked_tiny  # noqa: E402
# the ranked driver end to end, by import: tier-1 runs what
# `benchmark/tests/test_ranked_cell.py` runs by path
from test_ranked_cell import (  # noqa: E402,F401
    ranked_root, sound, test_float8_control_is_not_correct_ranked,
    test_planted_ranking_fault_is_not_correct,
    test_sound_ranked_run_is_correct_and_well_formed)
from drivers import train_ranked  # noqa: E402
from harness import data_ranked, reference_ranked, work_ranked  # noqa: E402
from lightgbm_tpu.config import Config  # noqa: E402
from lightgbm_tpu.io.dataset import Metadata  # noqa: E402
from lightgbm_tpu.objectives import create_objective  # noqa: E402
from lightgbm_tpu.utils import spans  # noqa: E402


# -- the reference's gradients against the program's -----------------------
LENGTHS = [1, 60, 7, 33, 2, 48, 19, 60, 5, 26, 1, 41]
PARAMS = {"objective": "lambdarank", "sigmoid": 1.0,
          "label_gain": "0,1,3,7,15"}


def _objective(label, boundaries, weights=None, **params):
    objective = create_objective(Config.from_params(
        {k: str(v) for k, v in {**PARAMS, **params}.items()}))
    objective.init(Metadata(label=label, weights=weights,
                            query_boundaries=boundaries), len(label))
    return objective


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("max_position", [3, 20])
def test_reference_gradients_equal_the_programs(max_position, weighted):
    """Seeded random scores on queries of 1 to 60 documents, a
    one-document query among them, scores rounded to one decimal so
    that most documents tie with another (ties go by position in both),
    one query all tied.

    Tolerance 2e-5 of the largest |value|.  Both sides work in float32:
    a document's lambda is a difference of two sums of up to 60 pair
    terms, added in another order on each side (2 x 60 x 6e-8 of the
    sums), the reference takes 1 / log2(2 + rank) in float32 where the
    program rounds a float64 table (1e-7), and the maximum DCG is a
    float64 sum against a float32 one (1e-7): some 1e-5 in all.  A
    missed tie rule, truncation or weight moves a value by its own size."""
    rng = np.random.default_rng(20261003 + max_position)
    boundaries = np.concatenate([[0], np.cumsum(LENGTHS)]).astype(np.int32)
    n = int(boundaries[-1])
    label = rng.choice(5, n, p=[0.6, 0.15, 0.1, 0.1, 0.05]).astype(np.float32)
    score = np.round(rng.normal(0.0, 0.4, n), 1).astype(np.float32)
    score[boundaries[3]:boundaries[4]] = 0.3            # one query all tied
    weights = (rng.uniform(0.5, 2.0, n).astype(np.float32) if weighted
               else None)
    params = {**PARAMS, "max_position": max_position}

    program = _objective(label, boundaries, weights,
                         max_position=max_position)
    want_lam, want_hes = (np.asarray(a)[:n] for a in
                          program.get_gradients(jnp.asarray(score)))
    n_pad = n + 13                                      # rows past the data
    ranker = reference_ranked.Ranker(label, boundaries, params, n_pad,
                                     weights)
    lam, hes = (np.asarray(a) for a in ranker.gradients(
        jnp.asarray(np.pad(score, (0, n_pad - n)))))
    assert not lam[n:].any() and not hes[n:].any()
    assert np.abs(want_lam).max() > 0.0
    for got, want in ((lam[:n], want_lam), (hes[:n], want_hes)):
        assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    # a query of one document has no pair
    assert LENGTHS[0] == LENGTHS[10] == 1
    assert lam[0] == 0.0 and lam[boundaries[10]] == 0.0


# -- the ranked rows --------------------------------------------------------
def _queries(rows):
    """The queries of `rows` as (bins bytes, labels bytes), file order."""
    b = rows.query_boundaries
    return [(rows.bins[:, b[q]:b[q + 1]].tobytes(),
             rows.label[b[q]:b[q + 1]].tobytes()) for q in range(len(b) - 1)]


def test_ranked_rows_are_whole_queries_in_a_seeded_order():
    cfg = ranked_tiny.tiny_config()
    make = lambda seed: data_ranked.make_ranked_rows(
        cfg["data"], cfg["num_data"], cfg["num_queries"], 255, seed)
    a, b, again = make(2 ** 31 + 5), make(7), make(2 ** 31 + 5)
    law = cfg["data"]["query_length"]
    for rows in (a, b):
        lengths = np.diff(rows.query_boundaries)
        # the totals are exact, the queries contiguous and in bounds
        assert rows.bins.shape == (12, cfg["num_data"])
        assert len(lengths) == cfg["num_queries"]
        assert rows.query_boundaries[0] == 0
        assert rows.query_boundaries[-1] == cfg["num_data"]
        assert lengths.min() == law["min"] and lengths.max() == law["max"]
        assert set(np.unique(rows.label)) <= {0.0, 1.0, 2.0, 3.0, 4.0}
    # the same seed gives the same rows; another seed another order
    assert np.array_equal(a.bins, again.bins)
    assert np.array_equal(a.label, again.label)
    assert not np.array_equal(a.query_boundaries, b.query_boundaries)
    # ... of WHOLE queries: each query of one seed is a query of the other,
    # its documents and their labels in the same order inside it
    assert sorted(_queries(a)) == sorted(_queries(b))
    assert _queries(a) != _queries(b)


def test_lengths_tile_to_the_exact_total():
    rng = np.random.default_rng(1)
    law = {"min": 1, "mean": 316.6, "max": 439, "cv": 0.35}
    lengths = data_ranked.block_lengths(law, 1024, 33018, 10454629, rng)
    whole, rest = divmod(33018, 1024)
    assert lengths.sum() * whole + lengths[:rest].sum() == 10454629
    assert lengths.min() == 1 and lengths.max() == 439
    assert abs(lengths.std() / lengths.mean() - 0.35) < 0.02


# -- what the deployment added to the program -------------------------------
@pytest.fixture(scope="module")
def tiny_job():
    cfg = ranked_tiny.tiny_config()
    rows = data_ranked.make_ranked_rows(cfg["data"], cfg["num_data"],
                                        cfg["num_queries"], 255, 11)
    return cfg, rows


def test_pair_counters_are_the_formula_of_the_lengths(tiny_job):
    cfg, rows = tiny_job
    lengths = np.diff(rows.query_boundaries).astype(np.int64)
    c = _objective(rows.label, rows.query_boundaries).trace_counters()
    lmax = int(lengths.max())
    q_block = min(max(1, (1 << 24) // lmax ** 2), len(lengths))
    blocks = -(-len(lengths) // q_block)
    assert c == {"pairs_padded": blocks * q_block * lmax ** 2,
                 "pairs_real": int((lengths ** 2).sum()),
                 "queries": len(lengths), "lmax": lmax}
    # the benchmark's count of the work is the queries' own, whatever the
    # padding: one long query more pads every block to its length
    assert work_ranked.pair_cells(lengths) == c["pairs_real"]
    longer = np.append(lengths, 200)
    b = np.concatenate([[0], np.cumsum(longer)]).astype(np.int32)
    padded = _objective(np.zeros(b[-1], np.float32), b).trace_counters()
    assert padded["pairs_padded"] > 8 * c["pairs_padded"]
    assert work_ranked.pair_cells(longer) == c["pairs_real"] + 200 ** 2
    assert work_ranked.pair_ops(longer) == (
        work_ranked.OPS_PER_CELL * work_ranked.pair_cells(longer))
    # an objective with no pair pass carries no counter
    assert create_objective(Config.from_params(
        {"objective": "binary"})).trace_counters() == {}


def test_a_resort_carries_row_slot_and_the_flush_the_counters(tiny_job,
                                                              tmp_path):
    """A traced period of the tiny job: the re-sorting dispatch moves
    the bins, scores, bag, order and lambdarank's `row_slot` in the one
    gather of words (`carried` 5, `taken` 0, `word_rows` 4 + a word for
    every four features); the flush's span carries the objective's
    counters."""
    from harness import scopes
    cfg, rows = tiny_job
    booster = train_ranked.build_booster(cfg, rows, on_tpu=False)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        done = 0
        while done < 5:
            done += booster.train_segment(5 - done, is_eval=False)[1]
        assert len(booster.models) == 5
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    host = scopes.read_trace(path).host
    resorts = [s.stats for s in host if s.name == spans.ENQUEUE
               and s.stats["kind"] == "resort"]
    assert len(resorts) == 2            # trees 0 and 4
    # (the harness's reader leaves a stat of value 0 out)
    assert all((s["carried"], s.get("taken", 0)) == (5, 0) for s in resorts)
    features = booster.bins_dev.shape[0]
    assert all(s["word_rows"] == 4 + -(-features // 4) for s in resorts)
    flushes = [s.stats for s in host if s.name == spans.FLUSH]
    want = booster.objective.trace_counters()
    assert flushes and want["pairs_padded"] > want["pairs_real"] > 0
    for stats in flushes:
        assert {k: stats[k] for k in want} == want
