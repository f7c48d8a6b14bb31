"""The names a training job shows under in a JAX profiler trace
(lightgbm_tpu/utils/spans.py): device scopes in every fused step, host
spans with their counts around the segment loop, named Pallas kernels,
the benchmark's copy of the lists, the start-up names among them
(tests/test_startup_spans.py has what those record).  All on the CPU: what
the scopes read on the chip is benchmark/phase_table.py's business.
"""

import ast
import glob
import json
import os
import re

import jax
import numpy as np
import pytest
from test_resort_rows import _forget_steps

import lightgbm_tpu as lgb
from lightgbm_tpu.models import gbdt
from lightgbm_tpu.ops import hist_pallas
from lightgbm_tpu.utils import spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "lightgbm_tpu")
SCOPE_RE = re.compile(r"lgbm\.[a-z_]+")

# what every fused step must name, and what each path adds
CORE = {spans.OBJECTIVE, spans.GROW, spans.HIST_ROOT, spans.HIST_SWEEP,
        spans.HIST_POOL, spans.GAIN_SCAN, spans.PARTITION,
        spans.TREE_UPDATE, spans.SCORE_UPDATE, spans.PACK_TREE}
RANK = (spans.RANK_GATHER, spans.RANK_SORT, spans.RANK_PAIRS)
PATHS = {
    # name: (params, classes, with a validation set, scopes beyond CORE)
    "plain": ({}, 1, True, {spans.VALID_UPDATE}),
    "reorder": ({"hist_impl": "pallas", "hist_reorder_every": 2}, 1, False,
                {spans.BLOCK_LIST, spans.RESORT}),
    "multiclass": ({"objective": "multiclass", "num_class": 3}, 3, False,
                   set()),
    # the class-wise step on the ordered path: one joint key, two words
    "multiclass_reorder": ({"objective": "multiclass", "num_class": 3,
                            "hist_impl": "pallas", "hist_reorder_every": 2},
                           3, False,
                           {spans.BLOCK_LIST, spans.RESORT, spans.CLASS_KEY}),
    "dart": ({"boosting_type": "dart", "drop_rate": 0.5}, 1, False,
             {spans.DART_BANK, spans.DART_DROP, spans.DART_NORMALIZE,
              spans.DART_REPLAY}),
    # the banked step on the ordered path: the bank rides the re-sort
    "dart_reorder": ({"boosting_type": "dart", "drop_rate": 0.5,
                      "hist_impl": "pallas", "hist_reorder_every": 2}, 1,
                     False,
                     {spans.BLOCK_LIST, spans.RESORT, spans.DART_BANK,
                      spans.DART_DROP, spans.DART_NORMALIZE,
                      spans.DART_REPLAY, spans.DART_CARRY}),
    "sharded": ({"tree_learner": "data", "num_shards": 4}, 1, False,
                {spans.HIST_EXCHANGE}),
    "bagged": ({"bagging_fraction": 0.5, "bagging_freq": 1,
                "bag_compact": "on"}, 1, False,
               {spans.OOB_DESCENT, spans.BAG_ARRANGE}),
    # graded labels in queries of 16; the ordered path, as on the chip
    "lambdarank": ({"objective": "lambdarank", "hist_impl": "pallas",
                    "hist_reorder_every": 2}, 3, False,
                   {spans.BLOCK_LIST, spans.RESORT} | set(RANK)),
}


FEATURES = 6


def _data(classes, n=8192, f=FEATURES, seed=3):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f).astype(np.float32)
    z = x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.3 * rng.randn(n)
    if classes == 1:
        return x, (z > 0).astype(np.float32)
    return x, np.digitize(z, [-0.5, 0.5]).astype(np.float32)


def _train(extra, classes, valid, rounds):
    x, y = _data(classes)
    params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
              "verbose": -1, "device_type": "cpu", **extra}
    group = (np.full(len(y) // 16, 16, np.int32)
             if params["objective"] == "lambdarank" else None)
    train = lgb.Dataset(x, label=y, group=group)
    sets = [lgb.Dataset(x[:512], label=y[:512], reference=train)] * valid
    return lgb.train(params, train, num_boost_round=rounds, valid_sets=sets)


def _lowered_texts(path, monkeypatch, compiled=False):
    """Three rounds of the path; -> the lowered text of every executable
    it dispatched (`compiled`: the optimised HLO's, whose `op_name`s hold
    the whole name stack where a lowering's private functions name their
    operations from their own start)."""
    extra, classes, valid, _ = PATHS[path]
    texts = []
    cached = gbdt._get_fused_step

    def lowering_too(key, make):
        fn = cached(key, make)

        def call(*args):
            low = fn.lower(*args)
            texts.append(low.compile().as_text() if compiled
                         else low.as_text(debug_info=True))
            return fn(*args)
        return call

    monkeypatch.setattr(gbdt, "_get_fused_step", lowering_too)
    _train(extra, classes, valid, rounds=3)
    return "\n".join(texts)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_step_carries_its_scopes(path, monkeypatch):
    """The lowered text of every executable the path dispatches holds
    the scopes the path must have (a scope is HLO metadata: what is in
    the lowering is what a device trace shows)."""
    more = PATHS[path][3]
    found = set(SCOPE_RE.findall(_lowered_texts(path, monkeypatch)))
    assert CORE | more <= found, sorted((CORE | more) - found)
    assert found <= set(spans.DEVICE_SCOPES), found
    # no other objective enters lambdarank's scopes
    assert not (found & set(RANK)) - more, found


def test_rank_scopes_nest_inside_the_objective(monkeypatch):
    """lambdarank's three scopes lower under their names INSIDE
    `lgbm.objective`, in the re-sort step and in the scan alike: a reader
    that takes the last `lgbm.*` component sees the gathers, the sorts and
    the pair pass apart, and one that asks for `lgbm.objective` anywhere in
    the stack still finds all of the objective.  Each holds the operations
    it is named for."""
    text = _lowered_texts("lambdarank", monkeypatch, compiled=True)
    # (a reducer's own body names its one `add` from the reduce's start)
    names = {n for n in re.findall(r'op_name="([^"]*)"', text)
             if n.startswith("jit(")}
    for scope in RANK:
        inside = [n for n in names if scope in n]
        assert inside, scope
        strays = [n for n in inside
                  if "/%s/" % spans.OBJECTIVE not in n.split(scope)[0]]
        assert not strays, strays

    def ops(scope):
        return {n.rsplit("/", 1)[-1] for n in names
                if "/%s/" % scope in n}
    assert "gather" in ops(spans.RANK_GATHER)
    assert {"sort", "gather"} <= ops(spans.RANK_SORT)
    assert {"exp", "reduce_sum"} <= ops(spans.RANK_PAIRS)
    assert "sort" not in ops(spans.RANK_PAIRS) | ops(spans.RANK_GATHER)


def test_dart_scopes_nest_where_a_reader_expects_them(monkeypatch):
    """A replayed drop lowers under `lgbm.dart_replay` INSIDE the drop or
    the normalise, the bank's groups under `lgbm.dart_carry` INSIDE
    `lgbm.resort`: a reader that takes the last `lgbm.*` component sees
    them apart, and the append alone is left under `lgbm.dart_bank`."""
    text = _lowered_texts("dart_reorder", monkeypatch, compiled=True)
    names = {n for n in re.findall(r'op_name="([^"]*)"', text)
             if n.startswith("jit(")}

    def outer(scope):
        return {tuple(SCOPE_RE.findall(n.split(scope)[0])) for n in names
                if "/%s/" % scope in n}
    assert outer(spans.DART_REPLAY) == {(spans.DART_DROP,),
                                        (spans.DART_NORMALIZE,)}
    assert outer(spans.DART_CARRY) == {(spans.RESORT,)}
    assert outer(spans.DART_BANK) == {()}
    ops = {n.rsplit("/", 1)[-1] for n in names
           if "/%s/" % spans.DART_CARRY in n}
    assert {"gather", "dynamic_update_slice"} <= ops, ops


def test_class_key_nests_inside_the_resort(monkeypatch):
    """The class-wise step's key lowers under `lgbm.class_key` INSIDE
    `lgbm.resort`, and the sort it feeds takes at most three operands."""
    text = _lowered_texts("multiclass_reorder", monkeypatch, compiled=True)
    names = {n for n in re.findall(r'op_name="([^"]*)"', text)
             if "/%s/" % spans.CLASS_KEY in n}
    assert names
    assert {tuple(SCOPE_RE.findall(n.split(spans.CLASS_KEY)[0]))
            for n in names} == {(spans.RESORT,)}
    sorts = re.findall(r"= \(([^)]*)\) sort\(", text)
    assert sorts and all(len(s.split(",")) <= 3 for s in sorts), sorts


@pytest.mark.parametrize("path,scope", [("reorder", spans.RESORT),
                                        ("bagged", spans.BAG_ARRANGE),
                                        ("dart_reorder", spans.RESORT),
                                        ("multiclass_reorder", spans.RESORT)])
def test_resort_helper_lowers_under_its_scope(path, scope, monkeypatch):
    """Every operation `_resort_rows` makes (the sort, the gather of the
    stacked words, the wider arrays' gathers, the window's copies, the
    objective's rebuild) carries the caller's scope: `resort_tree_s`
    reads all of the re-sort, and `device_unscoped_pct` none of it.  A
    probe scope inside the helper marks its operations."""
    real = gbdt._resort_rows

    def probed(*args):
        with jax.named_scope("resort_rows_probe"):
            return real(*args)

    monkeypatch.setattr(gbdt, "_resort_rows", probed)
    _forget_steps()
    try:
        text = _lowered_texts(path, monkeypatch)
    finally:
        _forget_steps()
    marked = [name for name in re.findall(r'loc\("([^"]*)"', text)
              if "resort_rows_probe" in name]
    assert any(name.endswith("/sort") for name in marked), marked
    strays = [name for name in marked
              if "/%s/resort_rows_probe/" % scope not in name]
    assert not strays, strays


def test_no_name_outside_the_registry():
    """Every `lgbm.*` name in the package is in utils/spans.py, and no
    site passes a string literal where a constant belongs."""
    kinds = (spans.DEVICE_SCOPES, spans.HOST_SPANS, spans.STARTUP_SPANS)
    known = set().union(*kinds)
    assert len(known) == sum(len(k) for k in kinds)
    for path in glob.glob(os.path.join(PACKAGE, "**", "*.py"),
                          recursive=True):
        with open(path) as fh:
            src = fh.read()
        strangers = set(SCOPE_RE.findall(src)) - known
        assert not strangers, (path, strangers)
        if not path.endswith(os.path.join("utils", "spans.py")):
            literal = re.findall(
                r"(?:named_scope|TraceAnnotation|startup|stamp)"
                r"\(\s*[\"']", src)
            assert not literal, (path, literal)


# -- host spans ------------------------------------------------------------
ROUNDS = 12
SEGMENTED = {"hist_impl": "pallas", "iter_batch": 4, "hist_reorder_every": 4}


def _program_spans(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name in spans.HOST_SPANS]


@pytest.fixture(scope="module")
def traced_and_plain(tmp_path_factory):
    """The same segmented training job under the profiler and without."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the spans are the host tracer's
    d0 = gbdt.dispatch_count()
    with jax.profiler.trace(trace_dir, profiler_options=options):
        traced = _train(SEGMENTED, 1, 0, ROUNDS)
    dispatches = gbdt.dispatch_count() - d0
    plain = _train(SEGMENTED, 1, 0, ROUNDS)
    return _program_spans(trace_dir), dispatches, traced, plain


def test_spans_count_what_was_trained(traced_and_plain):
    found, dispatches, booster, _ = traced_and_plain
    by_name = {}
    for name, stats in found:
        by_name.setdefault(name, []).append(stats)
    assert len(by_name[spans.SEGMENT]) >= 3
    for name in (spans.SEGMENT, spans.ENQUEUE):
        assert sum(s["k"] for s in by_name[name]) == ROUNDS
    assert len(by_name[spans.ENQUEUE]) == dispatches
    assert {s["kind"] for s in by_name[spans.ENQUEUE]} == {"scan", "resort"}
    assert {s["kind"] for s in by_name[spans.ENQUEUE]} \
        <= set(spans.ENQUEUE_KINDS)
    # a re-sorting dispatch says what moved in the one gather of words
    # (the bins, scores, bag, row order, the binary objective's two
    # arrays), what followed the permutation by a gather of its own
    # (nothing) and the rows of the stacked matrix (five words and the
    # bins, four feature rows a word); no other dispatch has the stats
    for s in by_name[spans.ENQUEUE]:
        if s["kind"] == "resort":
            assert (s["carried"], s["taken"]) == (6, 0), s
            assert s["word_rows"] == 5 + -(-FEATURES // 4), s
        else:
            assert not {"carried", "taken", "word_rows"} & set(s), s
    assert sum(s["trees"] for s in by_name[spans.FLUSH]) == ROUNDS
    assert all(s["bytes"] > 0 for s in by_name[spans.FLUSH])
    # the ordered path's sweeps: a tree's root holds every one of the 8,192
    # rows, which lie in one row block, and each split's smaller child
    # fewer; seven sweeps a tree, one block each
    for s in by_name[spans.FLUSH]:
        assert (s["trees"] * 8192 < s["rows_swept"]
                <= s["blocks_swept"] * 8192), s
        assert s["blocks_swept"] == 7 * s["trees"], s
    assert len(by_name[spans.FLUSH_PULL]) == len(by_name[spans.FLUSH])
    assert [s["iter"] for s in by_name[spans.SEGMENT]] == sorted(
        s["iter"] for s in by_name[spans.SEGMENT])
    assert spans.HOST_INPUTS in by_name and spans.EVAL in by_name
    assert len(booster._gbdt.models) == ROUNDS


SAMPLING_STATS = ("bag_window", "bag_in_bag", "bag_draws", "feat_used")


@pytest.fixture(scope="module")
def sampled_spans(tmp_path_factory):
    """Five rounds that bag (a draw every second) and sample features."""
    trace_dir = str(tmp_path_factory.mktemp("sampled"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    extra = {**PATHS["bagged"][0], "bagging_freq": 2,
             "feature_fraction": 0.5}
    with jax.profiler.trace(trace_dir, profiler_options=options):
        _train(extra, 1, 0, rounds=5)
    return _program_spans(trace_dir)


def test_bag_arrangement_says_what_moved_together(sampled_spans):
    arranges = [s for name, s in sampled_spans
                if name == spans.ENQUEUE and s["kind"] == "arrange"]
    assert len(arranges) == 3       # bagging_freq=2: before rounds 0, 2, 4
    # the bins, the [1, N] scores row, the mask, the order, sign and
    # label_weight, all in the one gather
    assert all((s["carried"], s["taken"]) == (6, 0) for s in arranges)
    assert all(s["word_rows"] == 5 + -(-FEATURES // 4) for s in arranges)
    # the static window and the bag it holds: 8,192 rows, half in the bag
    assert all((s["window"], s["in_bag"]) == (4096, 4096) for s in arranges)
    # the earlier trees whose leaves order the rows: none before the
    # first tree, then the gbdt._RESORT_PREV grown last
    assert [s["keyed"] for s in arranges] == [0] + [gbdt._RESORT_PREV] * 2


def test_a_draw_has_its_span_and_the_flush_its_account(sampled_spans):
    """With sampling on: one `lgbm.bag_draw` a redraw, inside
    `lgbm.host_inputs`, with the round, the rows and the bag's count; every
    flush says the window, the bag, the draws since the flush before and
    the features a tree may split on."""
    draws = [s for name, s in sampled_spans if name == spans.BAG_DRAW]
    assert [(s["iter"], s["rows"], s["in_bag"]) for s in draws] == [
        (0, 8192, 4096), (2, 8192, 4096), (4, 8192, 4096)]
    flushes = [s for name, s in sampled_spans if name == spans.FLUSH]
    assert flushes and sum(s["trees"] for s in flushes) == 5
    assert sum(s["bag_draws"] for s in flushes) == len(draws)
    for s in flushes:
        assert (s["bag_window"], s["bag_in_bag"], s["feat_used"]) == (
            4096, 4096, 3), s


def test_no_sampling_no_draw_and_an_account_of_zeros(traced_and_plain):
    found = traced_and_plain[0]
    assert not [s for name, s in found if name == spans.BAG_DRAW]
    flushes = [s for name, s in found if name == spans.FLUSH]
    assert flushes
    for s in flushes:
        assert [s[k] for k in SAMPLING_STATS] == [0, 0, 0, 0], s
    assert all("window" not in s and "in_bag" not in s
               for name, s in found if name == spans.ENQUEUE)


def test_profiler_changes_no_bit(traced_and_plain):
    _, _, traced, plain = traced_and_plain
    assert traced.model_to_string() == plain.model_to_string()
    assert np.array_equal(np.asarray(traced._gbdt._training_score()),
                          np.asarray(plain._gbdt._training_score()))


# -- kernel names ----------------------------------------------------------
def _pallas_call_names():
    with open(hist_pallas.__file__) as fh:
        tree = ast.parse(fh.read())
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute)
             and n.func.attr == "pallas_call"]
    return [next((k.value.value for k in c.keywords if k.arg == "name"),
                 None) for c in calls]


@pytest.mark.parametrize("wrapper", [
    "leaf_histogram_masked", "leaf_histogram_blocklist",
    "leaf_partition_blocklist"])
def test_kernel_is_named_after_its_wrapper(wrapper):
    """A Pallas custom call's device event is named after the innermost
    component of its name stack: without a name= a scope around the call
    renames it and the benchmark's `%leaf_histogram` reader goes blind.
    The partition pass is no sweep: its name keeps it out of that sum."""
    names = _pallas_call_names()
    assert sorted(names) == ["leaf_histogram_blocklist",
                             "leaf_histogram_masked",
                             "leaf_partition_blocklist"], names
    assert wrapper in names and callable(getattr(hist_pallas, wrapper))
    assert sum(n.startswith("leaf_histogram") for n in names) == 2


# -- the benchmark's copy --------------------------------------------------
def _benchmark_names(name):
    with open(os.path.join(ROOT, "benchmark", "harness", name)) as fh:
        return json.load(fh)


@pytest.mark.parametrize("key,ours", [
    ("device_scopes", spans.DEVICE_SCOPES),
    ("host_spans", spans.HOST_SPANS),
    ("enqueue_kinds", spans.ENQUEUE_KINDS)])
def test_benchmark_copy_is_equal(key, ours):
    """The program's lists equal the UNION of the benchmark's scope files
    (`scopes.json`, accepted and not edited, `scopes_ranked.json`, what the
    ranking cell added, `scopes_bagged.json`, what the bagged cell added,
    `scopes_dart.json`, what the DART cell added, and `scopes_multi.json`,
    what the class-wise cell added), and in each file's
    grouping every scope a cell of that file can show feeds exactly one of
    its metrics: all the program's in the newest file, all but what a
    later file added in an older one."""
    base = _benchmark_names("scopes.json")
    ranked = _benchmark_names("scopes_ranked.json")
    bagged = _benchmark_names("scopes_bagged.json")
    dart = _benchmark_names("scopes_dart.json")
    multi = _benchmark_names("scopes_multi.json")
    assert (tuple(base[key]) + tuple(ranked.get(key, ()))
            + tuple(bagged.get(key, ())) + tuple(dart.get(key, ()))
            + tuple(multi.get(key, ()))) == ours
    if key == "device_scopes":
        grouped = [s for g in base["device_groups"].values() for s in g]
        assert sorted(grouped) == sorted(base[key])
        for added in (ranked, bagged):
            grouped = [s for g in added["device_groups"].values() for s in g]
            assert sorted(grouped) == sorted(set(ours) - set(dart[key])
                                             - set(multi[key]))
        for added, later in ((dart, multi[key]), (multi, ())):
            grouped = [s for g in added["device_groups"].values() for s in g]
            assert sorted(grouped) == sorted(set(ours) - set(later))
            # a part is read on its own AND inside its group
            for part in added["device_parts"].values():
                assert set(part) <= set(grouped)
    if key == "host_spans":
        grouped = {s for g in bagged["host_groups"].values()
                   for s in g["spans"]}
        assert grouped <= set(ours) and spans.BAG_DRAW in grouped
        grouped = {s for g in dart["host_groups"].values()
                   for s in g["spans"]}
        assert grouped <= set(ours) and spans.DART_DRAW in grouped


def test_benchmark_copy_of_the_start_up_names_is_equal():
    """`scopes_startup.json` (what the start-up readers added) holds the
    third kind, the stamps and the compile ledger's field names as the
    program has them; each group of its readers names what exists."""
    from lightgbm_tpu.utils import compile_cache
    names = _benchmark_names("scopes_startup.json")
    assert tuple(names["startup_spans"]) == spans.STARTUP_SPANS
    assert tuple(names["stamps"]) == spans.STAMPS
    assert tuple(names["ledger_fields"]) == compile_cache.LEDGER_FIELDS
    assert names["enqueue_context"] == spans.ENQUEUE
    grouped = [s for g in names["span_groups"].values() for s in g]
    assert sorted(grouped) == sorted(set(spans.STARTUP_SPANS)
                                     - {spans.STARTUP_DATASET})
    for group in names["ledger_groups"].values():
        assert set(group["fields"]) <= set(compile_cache.LEDGER_FIELDS)
