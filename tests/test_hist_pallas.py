"""Pallas histogram kernel parity vs the XLA oracle (interpret mode on CPU;
the same kernels run compiled on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.histogram import leaf_histogram, make_gvals
from lightgbm_tpu.ops.hist_pallas import (FEAT_BLOCK_CAP, MM_FEATS, OOB_BIT,
                                          PALLAS_ROW_BLOCK, T_MM_NS,
                                          T_STEP_NS, _feat_grid,
                                          fold_bag_bit, fold_leaf_mask,
                                          leaf_histogram_blocklist,
                                          leaf_histogram_masked,
                                          leaf_of, leaf_partition_blocklist,
                                          make_gh2, part_groups)


def _data(n, f, b, seed=0):
    rng = np.random.RandomState(seed)
    bins_t = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = (np.abs(rng.rand(n)) + 0.1).astype(np.float32)
    mask = rng.rand(n) < 0.6
    return bins_t, grad, hess, mask


@pytest.mark.parametrize("f,b", [(28, 255), (5, 17), (8, 256), (9, 64)])
def test_pallas_matches_xla_oracle(f, b):
    n = 512  # small row_block keeps interpret mode fast
    bins_t, grad, hess, mask = _data(n, f, b)
    gh2 = make_gh2(jnp.asarray(grad), jnp.asarray(hess))
    leaf_eff = fold_leaf_mask(jnp.zeros(n, jnp.int32), jnp.asarray(mask))
    got = leaf_histogram_masked(jnp.asarray(bins_t), gh2, leaf_eff,
                                jnp.int32(0), max_bin=b, row_block=128,
                                interpret=True)
    gv = make_gvals(jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask),
                    jnp.float32)
    want = leaf_histogram(jnp.asarray(bins_t), gv, max_bin=b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_masked_kernel_matches_xla_oracle():
    n, f, b = 768, 11, 255
    bins_t, grad, hess, _ = _data(n, f, b, seed=3)
    rng = np.random.RandomState(4)
    leaf_id = rng.randint(0, 5, size=n).astype(np.int32)
    bag = (rng.rand(n) < 0.8).astype(np.int32)
    target = 3
    gh2 = make_gh2(jnp.asarray(grad), jnp.asarray(hess))
    leaf_eff = fold_leaf_mask(jnp.asarray(leaf_id), jnp.asarray(bag) != 0)
    got = leaf_histogram_masked(
        jnp.asarray(bins_t), gh2, leaf_eff,
        jnp.int32(target), max_bin=b, row_block=128, interpret=True)
    mask = (leaf_id == target) & (bag != 0)
    gv = make_gvals(jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask),
                    jnp.float32)
    want = leaf_histogram(jnp.asarray(bins_t), gv, max_bin=b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def _sweep(kernel, bins, gh2, leaf_eff, target, nblocks, b):
    """One of the two kernels: every block, or blocks [1, nblocks - 1)."""
    kw = dict(max_bin=b, row_block=128, interpret=True)
    if kernel == "masked":
        return leaf_histogram_masked(bins, gh2, leaf_eff, target, **kw)
    blist = jnp.concatenate([jnp.arange(1, nblocks - 1, dtype=jnp.int32),
                             jnp.zeros(2, jnp.int32)])
    return leaf_histogram_blocklist(bins, gh2, leaf_eff, target, blist,
                                    jnp.int32(nblocks - 2), **kw)


@pytest.mark.parametrize("kernel", ["masked", "blocklist"])
@pytest.mark.parametrize("b", [63, 255])
@pytest.mark.parametrize("f", [8, 13, 28, 39, 47, 136])
def test_ragged_feature_block_reads_bins_in_place(f, b, kernel):
    """F that its feature block does not divide: the kernel reads the
    [F, N] matrix as it is (the last block runs past the array) and gives
    the same BITS as on a matrix the caller padded to whole blocks —
    with zeros, as the wrappers did, or with any other bytes: the rows
    past F reach only their own slices of the output, which are cut.
    F = 8 is the control (one block that fits, nothing to pad); F = 13,
    28, 39 and 47 are ONE block a little larger than the array; F = 136
    is two blocks of 72 with a ragged second."""
    n, nblocks, target = 1024, 8, 3
    bins_t, grad, hess, _ = _data(n, f, b, seed=f + b)
    rng = np.random.RandomState(f)
    leaf_id = rng.randint(2, 5, size=n).astype(np.int32)
    if kernel != "masked":      # the target's rows lie in the swept range
        leaf_id[:128] = 0
        leaf_id[-128:] = 0
    bag = rng.rand(n) < 0.8
    gh2 = make_gh2(jnp.asarray(grad), jnp.asarray(hess))
    leaf_eff = fold_leaf_mask(jnp.asarray(leaf_id), jnp.asarray(bag))
    fb, fpad, groups = _feat_grid(f)
    # the block is chosen from F: one block wherever the cap holds F
    # rounded up to 8, and never a block that lies wholly past the array
    assert (fpad > f) == (f % fb != 0) == (f != 8)
    assert (groups == 1) == (f <= FEAT_BLOCK_CAP) and fpad - f < fb
    got = _sweep(kernel, jnp.asarray(bins_t), gh2, leaf_eff,
                 jnp.int32(target), nblocks, b)
    assert got.shape == (f, b, 3)
    for tail in (np.zeros((fpad - f, n), np.uint8),
                 rng.randint(0, 256, size=(fpad - f, n)).astype(np.uint8)
                 ) if fpad > f else ():
        padded = _sweep(kernel, jnp.asarray(np.vstack([bins_t, tail])), gh2,
                        leaf_eff, jnp.int32(target), nblocks, b)
        assert padded.shape == (fpad, b, 3)
        assert jnp.array_equal(got, padded[:f])
    gv = make_gvals(jnp.asarray(grad), jnp.asarray(hess),
                    jnp.asarray((leaf_id == target) & bag), jnp.float32)
    want = leaf_histogram(jnp.asarray(bins_t), gv, max_bin=b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("f", [8, 13, 28, 39, 47, 136, 220, 2000])
def test_feature_block_is_chosen_from_f(f):
    """_feat_grid takes, of the multiples of 8 up to the cap, the block
    whose modelled cost of a row block (cdiv(F, fb) steps of fb / 4
    matmuls) is least, and of equals the widest."""
    fb, fpad, groups = _feat_grid(f)
    assert fb % 8 == 0 and 8 <= fb <= FEAT_BLOCK_CAP
    assert groups == -(-f // fb) and fpad == groups * fb
    assert fpad - fb < f <= fpad        # covers F, no block wholly past it

    def cost(b):
        return -(-f // b) * (b // MM_FEATS * T_MM_NS + T_STEP_NS)

    admissible = range(8, FEAT_BLOCK_CAP + 1, 8)
    least = min(cost(b) for b in admissible)
    assert cost(fb) == least
    assert fb == max(b for b in admissible if cost(b) == least)
    if f == 39:     # one group of 40: ten matmuls, one step a row block
        assert (fb, fpad, groups) == (40, 40, 1)
    if f == 220:    # no more matmuls or steps than 14 blocks of 16 ran
        assert fpad // MM_FEATS <= 56 and groups <= 14


@pytest.mark.parametrize("kernel", ["masked", "blocklist"])
@pytest.mark.parametrize("f", [39, 47])
def test_wide_block_gives_the_bits_of_sixteen_feature_slices(f, kernel):
    """One wide feature block gives, bit for bit, what blocks of 16 gave
    (the grouping before the block was chosen from F), here as sweeps of
    the matrix's 16-feature slices: a feature's histogram is the diagonal
    block of ITS matmul f // 4, slot f % 4, whatever else the step
    holds."""
    n, nblocks, b, target = 1024, 8, 255, 3
    bins_t, grad, hess, _ = _data(n, f, b, seed=f)
    rng = np.random.RandomState(f)
    leaf_id = rng.randint(2, 5, size=n).astype(np.int32)
    leaf_id[:128] = 0
    leaf_id[-128:] = 0
    gh2 = make_gh2(jnp.asarray(grad), jnp.asarray(hess))
    leaf_eff = fold_leaf_mask(jnp.asarray(leaf_id),
                              jnp.asarray(rng.rand(n) < 0.8))
    assert _feat_grid(f)[2] == 1 and _feat_grid(f)[0] > 16
    whole = _sweep(kernel, jnp.asarray(bins_t), gh2, leaf_eff,
                   jnp.int32(target), nblocks, b)
    slices = [_sweep(kernel, jnp.asarray(bins_t[i:i + 16]), gh2, leaf_eff,
                     jnp.int32(target), nblocks, b)
              for i in range(0, f, 16)]
    assert float(jnp.abs(whole).max()) > 0.0
    assert jnp.array_equal(whole, jnp.concatenate(slices))


def test_masked_kernel_empty_leaf():
    n, f, b = 256, 4, 32
    bins_t, grad, hess, _ = _data(n, f, b, seed=5)
    gh2 = make_gh2(jnp.asarray(grad), jnp.asarray(hess))
    got = leaf_histogram_masked(
        jnp.asarray(bins_t), gh2, jnp.zeros(n, jnp.int32),
        jnp.int32(7),  # no row has leaf 7
        max_bin=b, row_block=128, interpret=True)
    assert float(jnp.abs(got).max()) == 0.0


def test_grow_tree_pallas_impl_matches_xla():
    """End-to-end: trees grown with hist_impl=pallas (interpret via CPU)
    must match the xla implementation exactly."""
    from lightgbm_tpu.ops.grow import grow_tree
    from lightgbm_tpu.ops.split import SplitParams

    n = PALLAS_ROW_BLOCK  # satisfies the kernel's row-block constraint
    f, b = 6, 64
    rng = np.random.RandomState(0)
    bins_t = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    grad = (bins_t[0] / b - 0.5 + 0.2 * rng.randn(n)).astype(np.float32)
    hess = np.ones(n, dtype=np.float32)
    params = SplitParams(20, 1.0, 0.0, 0.0, 0.0)
    args = (jnp.asarray(bins_t), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones(n, dtype=bool), jnp.ones(f, dtype=bool))
    kw = dict(max_leaves=8, max_bin=b, params=params)
    tx, lx = grow_tree(*args, hist_impl="xla", **kw)
    tp, lp = grow_tree(*args, hist_impl="pallas", **kw)
    assert int(tp.num_leaves) == int(tx.num_leaves)
    nl = int(tx.num_leaves)
    np.testing.assert_array_equal(np.asarray(tp.split_feature)[:nl - 1],
                                  np.asarray(tx.split_feature)[:nl - 1])
    np.testing.assert_array_equal(np.asarray(tp.threshold_bin)[:nl - 1],
                                  np.asarray(tx.threshold_bin)[:nl - 1])
    np.testing.assert_array_equal(np.asarray(lp), np.asarray(lx))
    np.testing.assert_allclose(np.asarray(tp.leaf_value)[:nl],
                               np.asarray(tx.leaf_value)[:nl], rtol=1e-4)


# (blocks that hold the target leaf, the list handed to the kernel): the
# list's first len(blocks) entries are what the grid's run-time bound
# covers, whatever stands behind them
@pytest.mark.parametrize("occupied,blist", [
    ((), [5, 0, 1, 2, 3, 4]),                  # an empty leaf: n_active 0
    ((3,), [3, 0, 1, 2, 4, 5]),                # one block
    ((1, 4), [1, 4, 0, 0, 0, 0]),              # two
    ((0, 1, 2, 3, 4, 5), [0, 1, 2, 3, 4, 5]),  # every block
    ((2, 4, 5), [2, 4, 5, 0, 1, 3]),           # none at the array's front
], ids=["empty", "one", "two", "all", "not_at_front"])
def test_blocklist_kernel_bit_identical_to_masked(occupied, blist):
    """Sweeping only the occupied blocks must be BIT-identical to the
    full masked sweep: skipped blocks contribute exact +0.0f.  The grid
    runs len(occupied) row steps (one for an empty leaf), a bound the
    kernel reads at run time."""
    from lightgbm_tpu.ops.hist_pallas import (leaf_histogram_blocklist,
                                              leaf_histogram_masked,
                                              make_gh2)
    n = 8192 * 6
    rng = np.random.RandomState(3)
    bins = jnp.asarray(rng.randint(0, 255, size=(5, n)), dtype=jnp.uint8)
    gh2 = make_gh2(jnp.asarray(rng.randn(n), jnp.float32),
                   jnp.asarray(rng.rand(n), jnp.float32))
    leaf = np.ones(n, np.int32)
    for b in occupied:
        s = 8192 * b
        leaf[s:s + 8192] = np.where(rng.rand(8192) < 0.4, 3, 2)
    leaf = jnp.asarray(leaf)
    ref = leaf_histogram_masked(bins, gh2, leaf, jnp.int32(3),
                                max_bin=255, interpret=True)
    got = leaf_histogram_blocklist(bins, gh2, leaf, jnp.int32(3),
                                   jnp.asarray(blist, jnp.int32),
                                   jnp.int32(len(occupied)), max_bin=255,
                                   interpret=True)
    assert jnp.array_equal(ref, got)
    assert bool(occupied) == bool(float(jnp.abs(got).max()) > 0.0)


def _grown(case, ranged):
    """(tree, leaf_id) of one tree over four row blocks, the block-list
    mode on or off, in one of the settings that reach it."""
    from lightgbm_tpu.ops.grow import grow_tree, grow_tree_bagged
    from lightgbm_tpu.ops.split import SplitParams
    n = 8192 * 4
    f, b = 6, 64
    rng = np.random.RandomState(0)
    bins_t = rng.randint(0, b, size=(f, n)).astype(np.uint8)
    grad = (bins_t[0] / b - 0.5 + 0.2 * rng.randn(n)).astype(np.float32)
    hess = np.ones(n, dtype=np.float32)
    bag = rng.rand(n) < 0.9   # bagging must also be exact
    kw = dict(max_leaves=8, max_bin=b, hist_impl="pallas", ranged=ranged,
              params=SplitParams(20, 1.0, 0.0, 0.0, 0.0))
    grow = grow_tree
    if case == "pool":
        # two slots for eight leaves: parents are evicted and recomputed
        kw["hist_slots"] = 2
    elif case == "window":
        # in-bag rows first, a window of three blocks that holds them
        # all and some out-of-bag rows; the fourth block is replayed
        bag = np.arange(n) < 8192 * 2 + 5000
        kw["bag_rows"] = 8192 * 3
        grow = grow_tree_bagged
    elif case == "shards":
        from jax.sharding import Mesh
        from lightgbm_tpu.parallel.mesh import (DATA_AXIS, P,
                                                _sharded_grow_fn)
        grow = _sharded_grow_fn(
            Mesh(np.array(jax.devices()[:2]), (DATA_AXIS,)),
            dict(kw, psum_axis=DATA_AXIS, num_shards=2),
            in_specs=(P(None, DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS), P(None)),
            leaf_id_spec=P(DATA_AXIS))
        kw = {}
    return grow(jnp.asarray(bins_t), jnp.asarray(grad), jnp.asarray(hess),
                jnp.asarray(bag), jnp.ones(f, dtype=bool), **kw)


@pytest.mark.parametrize("case", ["bag", "pool", "shards", "window"])
def test_grow_tree_ranged_bit_identical(case):
    """ranged=True (block-list sweeps, the partition pass over the split
    leaf's own blocks, occupancy as state) must grow the IDENTICAL tree,
    and return the identical leaf ids, to the plain pallas full sweep
    with its vectorised compare, for the same row order: under a bag
    mask, with a histogram pool that evicts parents (the recompute reads
    the ids before the pass writes them in place), on two shards under
    psum_axis (occupancy, lists and grids are shard-local), and through
    grow_tree_bagged's window."""
    t0, l0 = _grown(case, ranged=False)
    t1, l1 = _grown(case, ranged=True)
    assert int(t0.num_leaves) == int(t1.num_leaves) == 8
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    counters = ("blocks_swept", "grid_rows", "partition_blocks",
                "rows_swept")
    for fld in t0._fields:
        if fld not in counters:
            np.testing.assert_array_equal(np.asarray(getattr(t0, fld)),
                                          np.asarray(getattr(t1, fld)))
    assert [int(getattr(t0, c)) for c in counters] == [0, 0, 0, 0]
    assert all(int(getattr(t1, c)) > 0 for c in counters)


def _partition_case(case):
    """(row blocks, part_blocks, listed groups, list handed over, keep)"""
    return {
        # groups in any order behind the listed ones; ascending in front
        "random": (12, 2, [1, 2, 4], [1, 2, 4, 5, 3, 0], True),
        "one_block_groups": (6, 1, [0, 3, 5], [0, 3, 5, 1, 2, 4], True),
        "every_group": (8, 4, [0, 1], [0, 1], True),
        # 7 blocks in groups of 4: the second group runs past the arrays
        "ragged_last": (7, 4, [0, 1], [0, 1], True),
        "ragged_alone": (9, 4, [2], [2, 0, 1], True),
        # an empty list runs one step over its first entry, moving nothing
        "empty": (8, 2, [], [3, 0, 1, 2], True),
        # growth has stopped: one step that matches nothing
        "keep_false": (8, 2, [0, 2], [0, 2, 1, 3], False),
    }[case]


@pytest.mark.parametrize("case", ["random", "one_block_groups",
                                  "every_group", "ragged_last",
                                  "ragged_alone", "empty", "keep_false"])
def test_partition_kernel_against_numpy_recount(case):
    """leaf_partition_blocklist against a numpy recount: the ids (rows of
    the split leaf over the threshold take the new leaf under their own
    bag bit; a group the list does not name keeps every byte, as does
    every group when keep is False or the list is empty) and, of every
    block of a listed group, which child has a row there (the rows that
    stay, the rows that went), any row and in-bag row."""
    nblocks, pb, listed, glist, keep = _partition_case(case)
    rb, f, bl, right, feature, thr = 256, 13, 2, 9, 11, 120
    n = nblocks * rb
    assert part_groups(nblocks, pb) == len(glist)
    rng = np.random.RandomState(nblocks * pb)
    bins = rng.randint(0, 255, size=(f, n)).astype(np.uint8)
    leaf = rng.randint(0, 5, size=n).astype(np.int32)
    leaf[:rb] = 1                               # a block without the leaf
    bag = rng.rand(n) < 0.7
    bag[rb:2 * rb] = False                      # a block wholly out of bag
    ids = np.asarray(fold_bag_bit(jnp.asarray(bag))) | leaf
    assert (np.asarray(leaf_of(jnp.asarray(ids))) == leaf).all()
    got, left, went = leaf_partition_blocklist(
        jnp.asarray(bins), jnp.asarray(ids), jnp.asarray(glist, jnp.int32),
        jnp.int32(len(listed)), bl, right, feature, thr, keep,
        row_block=rb, part_blocks=pb, interpret=True)
    got, left, went = np.asarray(got), np.asarray(left), np.asarray(went)
    assert left.shape == went.shape == (2, nblocks)
    want = ids.copy()
    visited = [b for g in (listed if keep else ())
               for b in range(g * pb, min((g + 1) * pb, nblocks))]
    for blk in visited:
        rows = slice(blk * rb, (blk + 1) * rb)
        mine = leaf[rows] == bl
        goes = mine & (bins[feature, rows] > thr)
        stays = mine & ~goes
        want[rows] = np.where(goes, right | (ids[rows] & OOB_BIT), ids[rows])
        assert list(left[:, blk]) == [stays.any(), (stays & bag[rows]).any()]
        assert list(went[:, blk]) == [goes.any(), (goes & bag[rows]).any()]
    np.testing.assert_array_equal(got, want)
    assert (got != ids).any() == bool(visited)


def test_every_pallas_wrapper_has_a_grower():
    """Every public leaf_histogram_* of ops/hist_pallas.py is imported by
    ops/grow.py: a kernel no grower reaches has never run in a cell, and
    still costs every edit of the layer (PERF.md section 6, PR 29)."""
    import ast

    from lightgbm_tpu.ops import grow, hist_pallas

    with open(grow.__file__) as fh:
        imported = {a.name for node in ast.walk(ast.parse(fh.read()))
                    if isinstance(node, ast.ImportFrom)
                    and node.module == "hist_pallas" for a in node.names}
    wrappers = sorted(n for n in dir(hist_pallas)
                      if n.startswith("leaf_histogram"))
    assert wrappers == ["leaf_histogram_blocklist", "leaf_histogram_masked"]
    assert set(wrappers) <= imported, set(wrappers) - imported
    assert "leaf_partition_blocklist" in imported


@pytest.mark.parametrize("source", ["command_line", "config_file",
                                    "python_api"])
@pytest.mark.parametrize("key,value", [
    ("hist_fused", "on"), ("hist_acc", "bf16"), ("hist_compact", "on")])
def test_removed_kernel_keys_are_unknown(key, value, source, tmp_path):
    """hist_fused, hist_acc and hist_compact chose among kernel forks
    that are gone (PR 29).  They are no fields of Config, get what any
    key the program does not know gets (read, then ignored: no alias, no
    check of the value, so `hist_impl=xla hist_acc=bf16` is no fatal any
    more), and a job that still names one trains the default path's model
    to the byte."""
    import dataclasses

    import lightgbm_tpu as lgb
    from conftest import write_tsv
    from lightgbm_tpu import cli
    from lightgbm_tpu.config import ALIAS_TABLE, Config, load_parameters

    assert key not in {f.name for f in dataclasses.fields(Config)}
    assert key not in ALIAS_TABLE and key not in ALIAS_TABLE.values()
    data = str(tmp_path / "train.tsv")
    rng = np.random.RandomState(11)
    x = rng.randn(300, 4)
    write_tsv(data, x[:, 0] + x[:, 1] * x[:, 2] + 0.3 * rng.randn(300) > 0,
              x)
    base = ["task=train", "data=" + data, "objective=binary",
            "num_trees=3", "num_leaves=7", "min_data_in_leaf=5",
            "hist_impl=xla", "device_type=cpu", "verbose=-1"]

    def train(tag, said=None):
        if source == "python_api":
            params = dict(kv.split("=") for kv in base[2:])
            if said is not None:
                params[key] = said
            return lgb.train(params, lgb.Dataset(data, params=params),
                             verbose_eval=False).model_to_string()
        out = tmp_path / (tag + ".txt")
        argv = base + ["output_model=%s" % out]
        if said is None:
            pass
        elif source == "config_file":
            conf = tmp_path / (tag + ".conf")
            conf.write_text("# a job from before PR 29\n%s = %s\n"
                            % (key, said))
            argv.append("config=%s" % conf)
        else:
            argv.append("%s=%s" % (key, said))
        params = load_parameters(argv)
        assert (key in params) == (said is not None)
        assert Config.from_params(params) == Config.from_params(
            {k: v for k, v in params.items() if k != key})
        assert cli.main(argv) == 0
        return out.read_text()

    want = train("plain")
    assert train("named", value) == want
    assert train("garbled", "maybe") == want
