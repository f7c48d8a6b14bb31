"""Cross-lowering of the Pallas histogram kernels for the TPU, from this
CPU host: `fn.trace(...).lower(lowering_platforms=("tpu",))` runs the
Pallas -> Mosaic front end (not the Mosaic back end) and needs no chip.

It catches the class of breakage that shipped once already: a kernel
that only ever ran interpreted and that the TPU lowering refuses
(unimplemented primitives, block shapes the lowering rejects).  What
only a chip can say — the Mosaic back end, VMEM, numerics — is
chip_smoke.py's job.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from test_bins_in_place import N as STEP_ROWS
from test_bins_in_place import (_steps_of_a_training_job,  # noqa: F401
                                traces_forgotten)

from lightgbm_tpu.ops import hist_pallas as hp

S = jax.ShapeDtypeStruct
N = 8 * hp.PALLAS_ROW_BLOCK
I32 = S((), jnp.int32)


def _lower_for_tpu(fn, args, **statics):
    return fn.trace(*args, **statics).lower(lowering_platforms=("tpu",))


def _sweep_args(f):
    return (S((f, N), jnp.uint8), S((2, N), jnp.float32),
            S((N,), jnp.int32), I32)


def _pads_of_u8(low):
    """The `stablehlo.pad` lines of a lowering that take a uint8 operand:
    a wrapper that copies the bin matrix to whole feature blocks."""
    return [line.strip() for line in low.as_text().splitlines()
            if "stablehlo.pad" in line and "xui8>" in line.split("->")[0]]


# both Pallas kernels: the masked full sweep and the ordered-partition
# block-list sweep, at one feature block (F = 8 ... 48: blocks of 8, 16,
# 32, 40 and 48, chosen from F) and at two (136: blocks of 72).
# F = 13, 28, 39 and 136 do not fill their blocks: the last block runs
# past the array (13, 28, 39: ONE block larger than the array; 136: a
# ragged second), and the kernels read the matrix in place — no wrapper
# pads it.  F = 8 and 48 fill their block and never had a pad: the
# cases nothing changes for.
@pytest.mark.parametrize("max_bin", [63, 255])
@pytest.mark.parametrize("f", [8, 13, 28, 39, 48, 136])
def test_default_path_kernels_lower_for_tpu(f, max_bin):
    nblocks = N // hp.PALLAS_ROW_BLOCK
    for fn, more in (
            (hp.leaf_histogram_masked, ()),
            # the block list and its length: the grid's row bound, traced
            (hp.leaf_histogram_blocklist, (S((nblocks,), jnp.int32), I32))):
        low = _lower_for_tpu(fn, _sweep_args(f) + more, max_bin=max_bin)
        assert "tpu_custom_call" in low.as_text(), fn
        assert not _pads_of_u8(low), (fn, _pads_of_u8(low))


@pytest.mark.parametrize("learner", ["serial", "data"])
def test_one_blocklist_kernel_a_step(learner, monkeypatch, traces_forgotten):
    """The re-sort step and the K scan of the ordered path, lowered for
    the TPU at 9 row blocks a shard: ONE leaf_histogram_blocklist kernel
    an executable, called from the root's sweep and the per-split sweep,
    its grid bounded at run time by the leaf's own block count.  (A
    ladder of compiled grid sizes held a kernel a rung, two at 9 blocks
    and three from 33 on, under a `lax.switch` at both call sites.)
    Under tree_learner=data no collective stands before a sweep: each
    shard's kernel runs to its own count, so `lgbm.block_list` holds no
    `pmax` (the rung's agreement) and the histogram `psum` is the
    exchange's only kind.  The run-time bound shipped, so both halves
    are checked."""
    shards = {"data": 4, "serial": 1}[learner]
    extra = ({"tree_learner": "data", "num_shards": shards}
             if shards > 1 else {})
    steps = _steps_of_a_training_job(
        monkeypatch, n=shards * 9 * hp.PALLAS_ROW_BLOCK, f=6, **extra)
    assert len(steps) == 2, len(steps)      # the re-sort step, a K=2 scan
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for make, shapes in steps:
        text = make().trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
        assert text.count("@tpu_custom_call") == 1
        assert len(re.findall(
            r"func\.func private @leaf_histogram_blocklist", text)) == 1
        assert text.count("call @leaf_histogram_blocklist") == 2
        exchanged = {name.rsplit("/", 1)[-1]
                     for name in re.findall(r'loc\("([^"]*)"', text)
                     if "lgbm.hist_exchange/" in name}
        assert exchanged == ({"psum", "add"} if shards > 1 else set())


def _ops_under(text, scope):
    """The sorts (as their operand count) and gathers (as the length of
    their index vector, 0 where it is no vector) of a lowering's
    StableHLO text under a named scope.  An operation is under the scope
    if its own location names it, or if it sits in a private function
    that is called, at any depth, from a site that does."""
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))

    def where(line):
        ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
        return names.get(ref.group(1), "") if ref else ""

    ops, calls, func, operands = [], [], None, None
    for line in text.splitlines():
        head = re.search(r"func\.func \w+ @(\w+)\(", line)
        call = re.search(r"call @(\w+)\(", line)
        if head:
            func = head.group(1)
        elif '"stablehlo.sort"(' in line:
            operands = line.split('"stablehlo.sort"(')[1].split(")")[0]
        elif operands and line.lstrip().startswith("}) :"):
            ops.append((func, "sort", operands.count("%"), where(line)))
            operands = None
        elif "stablehlo.gather" in line:
            index = re.search(r"tensor<(\d+)x1xi32>\) ->", line)
            ops.append((func, "gather", int(index.group(1)) if index else 0,
                        where(line)))
        elif call:
            calls.append((func, call.group(1), where(line)))
    inside = set()
    while True:
        more = {callee for f, callee, loc in calls
                if scope in loc or f in inside}
        if more <= inside:
            break
        inside |= more
    return sorted((kind, n) for f, kind, n, loc in ops
                  if scope in loc or f in inside)


def test_resort_step_moves_row_state_in_one_gather(monkeypatch,
                                                   traces_forgotten):
    """The re-sort of the default ordered path, lowered for the TPU: ONE
    sort (the key and the iota) and TWO gathers by its permutation, the
    bin matrix's and that of the stacked words: the five arrays of the
    binary objective's step with one row a position (scores, bag, row
    order, sign, label_weight).  A take of one such array costs 1.7 s at
    68M rows where the gather of all five costs 1.0 (PERF.md section 6,
    PR 28), so an array that falls off the stack must show here."""
    make, shapes = _steps_of_a_training_job(monkeypatch)[0]
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = make().trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert _ops_under(text, "lgbm.resort") == [
        ("gather", STEP_ROWS), ("gather", STEP_ROWS), ("sort", 2)]
    stacked = re.findall(r"stablehlo\.gather.*tensor<(\d+)x%dxui32>, "
                         % STEP_ROWS, text)
    assert stacked == ["5"], stacked
    # the reader sees the rest of the step too: the block list's argsort
    assert ("sort", 2) in _ops_under(text, "lgbm.block_list")
