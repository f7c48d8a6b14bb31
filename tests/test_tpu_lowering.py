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
from test_bins_in_place import F as STEP_FEATURES
from test_bins_in_place import N as STEP_ROWS
from test_bins_in_place import PARAMS as STEP_PARAMS
from test_bins_in_place import (_steps_of_a_training_job,  # noqa: F401
                                traces_forgotten)

from lightgbm_tpu.models import gbdt
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
    if max_bin == 255:      # the partition pass knows no bins: once an F
        # the matrix, the ids, the list of groups and its length, then
        # split leaf, new leaf, feature, threshold, keep
        low = _lower_for_tpu(hp.leaf_partition_blocklist, (
            S((f, N), jnp.uint8), S((N,), jnp.int32),
            S((hp.part_groups(nblocks),), jnp.int32), I32, I32, I32, I32,
            I32, S((), jnp.bool_)))
        assert "tpu_custom_call" in low.as_text()
        assert not _pads_of_u8(low), _pads_of_u8(low)


@pytest.mark.parametrize("learner", ["serial", "data"])
def test_one_blocklist_kernel_a_step(learner, monkeypatch, traces_forgotten):
    """The re-sort step and the K scan of the ordered path, lowered for
    the TPU at 9 row blocks a shard: ONE leaf_histogram_blocklist kernel
    an executable, called from the root's sweep and the per-split sweep,
    its grid bounded at run time by the leaf's own block count, and
    beside it ONE leaf_partition_blocklist kernel, called once a split.
    (A ladder of compiled grid sizes held a kernel a rung, two at 9
    blocks and three from 33 on, under a `lax.switch` at both call
    sites.)
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
        assert text.count("@tpu_custom_call") == 2
        for kernel, sites in (("leaf_histogram_blocklist", 2),
                              ("leaf_partition_blocklist", 1)):
            assert len(re.findall(
                r"func\.func private @%s" % kernel, text)) == 1
            assert text.count("call @%s" % kernel) == sites
        exchanged = {name.rsplit("/", 1)[-1]
                     for name in re.findall(r'loc\("([^"]*)"', text)
                     if "lgbm.hist_exchange/" in name}
        assert exchanged == ({"psum", "add"} if shards > 1 else set())


def _row_sized(jaxpr, n, kernel, inside=False, found=None):
    """The equations of a traced step that hold a value with a dimension
    of n rows INSIDE the scan whose body calls the Pallas kernel named
    (the grow scan: a step a split), the Pallas calls themselves left
    out.  Walks every sub-jaxpr (pjit, cond, scan, shard_map)."""
    found = [] if found is None else found

    def subs(eqn):
        for v in eqn.params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield j

    def calls_kernel(j):
        return any((e.primitive.name == "pallas_call"
                    and e.params["name"] == kernel)
                   or any(calls_kernel(s) for s in subs(e)
                          if e.primitive.name != "scan")
                   for e in j.eqns)

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        inner = list(subs(eqn))
        for j in inner:
            _row_sized(j, n, kernel, inside or (
                eqn.primitive.name == "scan" and calls_kernel(j)), found)
        if inside and not inner and any(
                n in getattr(v.aval, "shape", ())
                for v in list(eqn.invars) + list(eqn.outvars)):
            found.append(eqn.primitive.name)
    return found


@pytest.mark.parametrize("mode,kernel", [
    ("block_list", "leaf_partition_blocklist"),
    ("masked", "leaf_histogram_masked")])
def test_no_split_touches_every_row(mode, kernel, monkeypatch,
                                    traces_forgotten):
    """The scan body of the default step (a step a split), traced for the
    TPU: outside the two kernels, the partition pass and the sweep, NO
    operation has an operand or a result with a dimension of N rows: no
    compare, select or reduce over the leaf ids, no copy of them, nothing
    over the bin matrix but the reshape of the ids to full registers, a
    bitcast (until PR 34 the go-right compare and the occupancy scan
    each passed over all N ids at every split: 16.5% of a period at 68M
    rows).  The masked mode (hist_ordered=off) is the control: the
    reader finds its compares and selects."""
    extra = {} if mode == "block_list" else {"hist_ordered": "off"}
    steps = _steps_of_a_training_job(monkeypatch, f=6, **extra)
    assert steps
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for make, shapes in steps:
        traced = make().trace(*shapes)
        assert "tpu_custom_call" in traced.lower(
            lowering_platforms=("tpu",)).as_text()
        over_rows = set(_row_sized(traced.jaxpr.jaxpr, STEP_ROWS, kernel))
        if mode == "block_list":
            assert over_rows <= {"reshape"}, over_rows
        else:
            assert {"eq", "gt", "select_n"} <= over_rows, over_rows


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
    sort (the key and the iota) and ONE gather by its permutation, that
    of the stacked words: the five arrays of the binary objective's step
    with one row a position (scores, bag, row order, sign, label_weight)
    AND the bin matrix, four feature rows a word.  A take of one such
    array costs 1.7 s at 68M rows and the bins' own 2.1 s where the
    gather of all of them costs little more than the five's 1.2 (PERF.md
    section 6, PRs 28 and 36), so an array that falls off the stack must
    show here.  The key's replays of the trees before this one look up
    their own node tables (a gather of the padded node count each, the
    leaf count), never a row."""
    make, shapes = _steps_of_a_training_job(monkeypatch)[0]
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = make().trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    nodes = STEP_PARAMS["num_leaves"]
    assert _ops_under(text, "lgbm.resort") == (
        [("gather", nodes)] * gbdt._RESORT_PREV
        + [("gather", STEP_ROWS), ("sort", 2)])
    stacked = re.findall(r"stablehlo\.gather.*tensor<(\d+)x%dxui32>, "
                         % STEP_ROWS, text)
    assert stacked == [str(5 + -(-STEP_FEATURES // 4))], stacked
    # the reader sees the rest of the step too: the block list's argsort
    assert ("sort", 2) in _ops_under(text, "lgbm.block_list")


def test_bag_arrangement_moves_row_state_in_one_gather(monkeypatch,
                                                       traces_forgotten):
    """The same under `lgbm.bag_arrange`: the arrangement after a redraw
    sorts every row on one key, the bag's bit over the replayed leaves of
    the trees grown last (each replay a gather of the padded node count,
    never a row), and moves bins, scores, mask, order and the objective's
    two arrays in ONE gather of the rows."""
    steps = _steps_of_a_training_job(
        monkeypatch, bagging_fraction=0.5, bagging_freq=1, bag_compact="on")
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    texts = [make().trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
        for make, shapes in steps]
    (text,) = [t for t in dict.fromkeys(texts) if "lgbm.bag_arrange" in t]
    (*replays, gather, sort) = _ops_under(text, "lgbm.bag_arrange")
    assert replays == [("gather", STEP_PARAMS["num_leaves"])] * \
        gbdt._RESORT_PREV
    assert sort == ("sort", 2)
    assert gather[0] == "gather" and 0 < gather[1] <= STEP_ROWS, gather
    stacked = re.findall(r"stablehlo\.gather.*tensor<(\d+)x%dxui32>, "
                         % gather[1], text)
    assert stacked == [str(5 + -(-STEP_FEATURES // 4))], stacked
