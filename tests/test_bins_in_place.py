"""The resident bin matrix is read in place: no executable of the default
ordered training path copies it to whole feature blocks.

A pad of the [F, N] matrix to a multiple of the feature block, in a
kernel's wrapper, runs inside every branch of every split: a copy of the
whole matrix, 16% of a tree at 68M x 39 (PERF.md, PR 26).  The kernels'
own lowerings are pinned by tests/test_tpu_lowering.py; this guards the
WHOLE step, so that the copy cannot come in through a new wrapper or a
new branch: the re-sort step and a K=2 scan at F = 39 (a ragged third
feature block), lowered for the TPU from this host, hold no value of the
padded matrix's shape at all.

Lowered for the TPU, not for the CPU: interpreted, pallas_call itself
pads every operand to whole blocks, which says nothing of the chip.
"""

import re

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models import gbdt
from lightgbm_tpu.ops.hist_pallas import PALLAS_ROW_BLOCK, _feat_grid

F = 39
N = 4 * PALLAS_ROW_BLOCK
PARAMS = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbose": -1, "device_type": "cpu", "hist_impl": "pallas",
          "hist_reorder_every": 3, "iter_batch": 2}


def _steps_of_a_training_job(monkeypatch, n=N, f=F, **extra):
    """(make, argument shapes) of every fused executable that three
    rounds of the ordered path dispatch on this host."""
    steps = []
    cached = gbdt._get_fused_step

    def recording(key, make):
        fn = cached(key, make)

        def call(*args):
            steps.append((make, jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)))
            return fn(*args)
        return call

    rng = np.random.RandomState(7)
    x = rng.randn(n, f).astype(np.float32)
    y = (x[:, 0] + x[:, 1] * x[:, 2] + 0.3 * rng.randn(n) > 0)
    monkeypatch.setattr(gbdt, "_get_fused_step", recording)
    lgb.train({**PARAMS, **extra}, lgb.Dataset(x, label=y.astype(np.float32)),
              num_boost_round=3)
    return steps


@pytest.fixture
def traces_forgotten():
    """Traces made for a pretended TPU backend must not outlive the test:
    the next test of this process that trains would find grow_tree's
    uninterpreted kernels in jax's caches."""
    yield
    jax.clear_caches()


def test_no_step_copies_the_bin_matrix(monkeypatch, traces_forgotten):
    steps = _steps_of_a_training_job(monkeypatch)
    assert len(steps) == 2, len(steps)      # the re-sort step, a K=2 scan
    # trace anew as the chip would: grow_tree asks the backend whether to
    # interpret its kernels, and its traces for this host are cached
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bins = "tensor<%dx%dxui8>" % (F, N)
    padded = "tensor<%dx%dxui8>" % (_feat_grid(F)[1], N)
    for make, shapes in steps:
        text = make().trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text, "no compiled kernel"
        assert bins in text
        pads = [line.strip() for line in text.splitlines()
                if re.search(r"stablehlo\.pad\b.*: \(%s" % re.escape(bins),
                             line)]
        assert not pads, pads
        assert padded not in text
