"""Data-parallel training over a JAX device mesh.

This module is the TPU-native replacement for the reference's entire
distributed stack (src/network/: Bruck allgather + recursive-halving
reduce-scatter over sockets/MPI, and src/treelearner/
data_parallel_tree_learner.cpp): rows are sharded along N across a 1-D
`data` mesh axis; inside the jitted grower each shard builds histograms for
its rows and a `jax.lax.psum` over the axis makes them global — the moral
equivalent of the reference's ReduceScatter of histogram buffers
(data_parallel_tree_learner.cpp:124-154) with XLA owning the ring schedule
over ICI/DCN.  Every shard then computes the identical global best split
(same invariant as the reference's global counts,
data_parallel_tree_learner.cpp:226-232) and applies it to its local rows,
so tree arrays come out replicated and leaf_id stays shard-local.

Multi-host scaling needs no extra code here: initialize
jax.distributed and build the mesh over all devices; XLA routes the psum
over ICI within a slice and DCN across slices.

Iteration batching (config.iter_batch) composes with this design by
putting its lax.scan INSIDE the shard_map body (models/gbdt.py
_batch_iters wraps the step closure BEFORE it reaches shard_map below):
each shard iterates its local rows through K boosting steps, the
per-step psum/all-gather collectives are exactly the K=1 ones (issued
K times inside the loop), and the stacked per-iteration inputs/outputs
([K, F] feature masks in, [K, T_ints]/[K, T_floats] packed trees out)
ride the replicated P() specs unchanged — P() constrains no axis, so
the extra leading K dimension needs no new partition rules.
check_vma=False in the wrapper is what permits replicated outputs from
loop-carried computations.
"""

from __future__ import annotations

__jax_free__ = False  # device mesh layer: jax by design

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.grow import TreeArrays, grow_tree
from ..ops.split import SplitParams

DATA_AXIS = "data"
FEATURE_AXIS = "feature"


def shard_map(fn, *, mesh: Mesh, in_specs, out_specs):
    """jax.shard_map with check_vma off (module docstring: replicated
    outputs from loop-carried computations).  Every shard_map in this
    package goes through here."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(num_shards: int = 0, axis: str = DATA_AXIS) -> Mesh:
    devs = jax.devices()
    if num_shards <= 0:
        num_shards = len(devs)
    if num_shards > len(devs):
        raise ValueError("num_shards=%d > %d available devices"
                         % (num_shards, len(devs)))
    return Mesh(np.array(devs[:num_shards]), (axis,))


def padded_size(n: int, num_shards: int) -> int:
    return ((n + num_shards - 1) // num_shards) * num_shards


def query_shard_bounds(query_boundaries, num_shards: int) -> np.ndarray:
    """Contiguous query -> shard partition for query-granular row
    sharding (lambdarank under tree_learner=data): shard s owns queries
    [bounds[s], bounds[s+1]), with each boundary placed on the query
    boundary nearest the ideal equal-row cut, so no query ever straddles
    a shard block — the invariant the query-sharded fused gradient state
    relies on (objectives.LambdarankNDCG.build_sharded_state).  Returns
    bounds [num_shards + 1] (query indices, non-decreasing; shards may
    be empty when there are fewer queries than shards)."""
    qb = np.asarray(query_boundaries, dtype=np.int64)
    nq = len(qb) - 1
    n = int(qb[-1])
    bounds = np.zeros(num_shards + 1, dtype=np.int64)
    bounds[num_shards] = nq
    for s in range(1, num_shards):
        t = n * s / num_shards
        i = int(np.searchsorted(qb, t))
        if i > nq or (i > 0 and qb[i] - t > t - qb[i - 1]):
            i -= 1
        bounds[s] = min(max(i, int(bounds[s - 1])), nq)
    return bounds


@dataclasses.dataclass
class RowShardLayout:
    """Query-granular device row layout for the data-parallel fused step
    with a query-structured objective (lambdarank): shard s's contiguous
    block of the row axis holds exactly the rows of queries
    [bounds[s], bounds[s+1]), padded to the common per-shard capacity
    `cap`, so no query ever straddles a shard and every shard's gradient
    state is self-contained.  `pos` maps LOCAL file rows to their local
    padded positions; gap rows (between a shard's last real row and its
    capacity) are permanently out-of-bag, exactly like trailing pad rows
    in the default layout."""
    cap: int                  # rows per shard block (row_unit-aligned)
    local_shards: int         # shards owned by THIS process
    n_pad: int                # local padded rows == cap * local_shards
    bounds: np.ndarray        # [local_shards + 1] query cuts (local)
    pos: np.ndarray           # [n_local] i32 file row -> padded position

    def place(self, arr: np.ndarray, fill=0) -> np.ndarray:
        """File-order rows (last axis) -> padded layout order."""
        out = np.full(arr.shape[:-1] + (self.n_pad,), fill,
                      dtype=arr.dtype)
        out[..., self.pos] = arr
        return out

    def unplace(self, arr: np.ndarray) -> np.ndarray:
        """Padded layout order (last axis) -> file-order rows."""
        return np.asarray(arr)[..., self.pos]


def query_shard_layout(query_boundaries, local_shards: int,
                       row_unit: int = 1, sync=None) -> RowShardLayout:
    """Build the RowShardLayout for this process's queries over its
    `local_shards` mesh devices.  `row_unit` aligns the per-shard
    capacity (the Pallas row block).  Multi-host passes `sync` (dist.
    sync_max_ints) so every process agrees on the global capacity —
    equal per-device blocks are required by the global array assembly."""
    qb = np.asarray(query_boundaries, dtype=np.int64)
    bounds = query_shard_bounds(qb, local_shards)
    rows = qb[bounds[1:]] - qb[bounds[:-1]]
    cap = max(int(rows.max()) if len(rows) else 1, 1)
    cap = -(-cap // row_unit) * row_unit
    if sync is not None:
        cap = int(sync([cap])[0])
    n = int(qb[-1])
    pos = np.empty(n, dtype=np.int32)
    for s in range(local_shards):
        a, b = int(qb[bounds[s]]), int(qb[bounds[s + 1]])
        pos[a:b] = s * cap + np.arange(b - a, dtype=np.int32)
    return RowShardLayout(cap=cap, local_shards=local_shards,
                          n_pad=cap * local_shards, bounds=bounds,
                          pos=pos)


def _put_sharded(arr: np.ndarray, mesh: Mesh, spec: P) -> jax.Array:
    """Place a host array with the given sharding.

    Single-process: arr is the GLOBAL array -> device_put.  Multi-host
    (jax.process_count() > 1): arr is this PROCESS'S row shard of the
    global array (each host loaded its own rows, io/dataset.py rank
    sharding) -> jax.make_array_from_process_local_data assembles the
    global sharded array without any cross-host copy; the global shape
    scales the DATA_AXIS dimension by the process count (equal local
    blocks — GBDT pads every process to the max local row count).
    device_put would be WRONG there: it treats its input as the same
    global value on every process."""
    sharding = NamedSharding(mesh, spec)
    pc = jax.process_count()
    if pc > 1:
        gshape = list(arr.shape)
        for dim, axis in enumerate(spec):
            if axis is not None:
                gshape[dim] *= pc
        return jax.make_array_from_process_local_data(sharding, arr,
                                                      tuple(gshape))
    return jax.device_put(arr, sharding)


def _put_row_blocks(arr: np.ndarray, n_pad: int, fill, mesh: Mesh,
                    spec: P) -> jax.Array:
    """Single-process placement of a host array whose LAST axis is rows,
    padded to n_pad and sharded over the data axis (`spec`): each block
    is cut from the array and put on its device, and only the block that
    runs past the array is padded — no padded copy of the whole array
    on the host (10.7 GB for a four-chip host's share of Criteo-1TB),
    and the transfers to the devices are in flight side by side."""
    devs = list(mesh.devices.flat)
    block = n_pad // len(devs)
    lead = [(0, 0)] * (arr.ndim - 1)
    pieces = []
    for i, dev in enumerate(devs):
        part = arr[..., i * block:(i + 1) * block]
        short = block - part.shape[-1]
        if short:
            part = np.pad(part, lead + [(0, short)], constant_values=fill)
        pieces.append(jax.device_put(part, dev))
    return jax.make_array_from_single_device_arrays(
        arr.shape[:-1] + (n_pad,), NamedSharding(mesh, spec), pieces)


def _pad_rows_and_put(arr: np.ndarray, n_pad: int, fill, mesh: Mesh,
                      spec: P) -> jax.Array:
    """Pad the last (row) axis to n_pad (this process's share of the
    global padded size under multi-host) and place with the given spec."""
    if (jax.process_count() == 1 and tuple(spec)
            == (None,) * (arr.ndim - 1) + (DATA_AXIS,)):
        return _put_row_blocks(arr, n_pad, fill, mesh, spec)
    pad = n_pad - arr.shape[-1]
    if pad:
        arr = np.pad(arr, [(0, 0)] * (arr.ndim - 1) + [(0, pad)],
                     constant_values=fill)
    return _put_sharded(arr, mesh, spec)


def _sharded_grow_fn(mesh: Mesh, grow_kw: dict, in_specs, leaf_id_spec: P):
    """jit(shard_map(grow_tree)) with replicated tree-array outputs — the
    shared scaffolding of the row- and feature-sharded growers."""
    fn = functools.partial(grow_tree, **grow_kw)
    tree_specs = TreeArrays(*([P()] * len(TreeArrays._fields)))
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=(tree_specs, leaf_id_spec)))


class ShardedGrower:
    """Grows trees with rows sharded over the mesh's data axis.

    voting_top_k > 0 switches the per-split histogram all-reduce to the
    PV-Tree voting protocol (tree_learner=voting, ops/grow.py)."""

    def __init__(self, mesh: Mesh, *, max_leaves: int, max_bin: int,
                 params: SplitParams, max_depth: int = -1,
                 row_chunk: int = 0, voting_top_k: int = 0,
                 hist_impl: str = "xla", hist_agg: str = "psum"):
        self.mesh = mesh
        self.num_shards = mesh.devices.size
        kw = dict(max_leaves=max_leaves, max_bin=max_bin, params=params,
                  max_depth=max_depth, row_chunk=row_chunk,
                  psum_axis=DATA_AXIS, voting_top_k=voting_top_k,
                  hist_impl=hist_impl, hist_agg=hist_agg,
                  num_shards=self.num_shards)
        self._grow = _sharded_grow_fn(
            mesh, kw,
            in_specs=(P(None, DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS), P(None)),
            leaf_id_spec=P(DATA_AXIS))
        self._permute = {}      # ndim -> jitted fn (permute_rows)
        self._inverse = None    # jitted fn (inverse_order)

    def bins_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(None, DATA_AXIS))

    def row_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(DATA_AXIS))

    def row_sharding_2d(self) -> NamedSharding:
        """[K, N] arrays sharded along N."""
        return NamedSharding(self.mesh, P(None, DATA_AXIS))

    def shard_bins(self, bins: np.ndarray, n_pad: int = 0) -> jax.Array:
        """Pad N to n_pad (default: the next multiple of the shard
        count) and place sharded."""
        n_pad = n_pad or padded_size(bins.shape[1], self.num_shards)
        return _pad_rows_and_put(bins, n_pad, 0, self.mesh,
                                 self.bins_sharding().spec)

    def shard_rows(self, arr: np.ndarray, n_pad: int, fill=0) -> jax.Array:
        return _pad_rows_and_put(
            arr, n_pad, fill, self.mesh,
            P(*([None] * (arr.ndim - 1) + [DATA_AXIS])))

    def put_spec(self, arr, spec: P) -> jax.Array:
        """Place a host array with an arbitrary PartitionSpec (multi-host:
        arr is this process's block of every sharded dim).  Used for
        gradient state whose leaves shard on a non-last axis (the
        query-sharded lambdarank blocks)."""
        return _put_sharded(np.asarray(arr), self.mesh, spec)

    def local_shard_count(self) -> int:
        """Mesh shards owned by THIS process (== num_shards single-host)."""
        if jax.process_count() == 1:
            return self.num_shards
        return sum(int(d.process_index == jax.process_index())
                   for d in self.mesh.devices.flat)

    def grow(self, bins_dev, grad, hess, bag_mask, feature_mask):
        return self._grow(bins_dev, grad, hess, bag_mask, feature_mask)

    def permute_rows(self, arr: jax.Array, order: jax.Array) -> jax.Array:
        """Permute an array (rows on its LAST axis) by a row-sharded
        GLOBAL-position order whose values stay inside each shard's own
        block — the ordered-partition invariant (re-sorts are
        shard-local), so the take is a cheap per-shard gather, never a
        cross-device one."""
        fn = self._permute.get(arr.ndim)
        if fn is None:
            def body(a, o):
                base = jax.lax.axis_index(DATA_AXIS) * o.shape[-1]
                return jnp.take(a, o - base, axis=-1)
            spec = P(*([None] * (arr.ndim - 1) + [DATA_AXIS]))
            fn = jax.jit(shard_map(
                body, mesh=self.mesh,
                in_specs=(spec, P(DATA_AXIS)), out_specs=spec))
            self._permute[arr.ndim] = fn
        return fn(arr, order)

    def inverse_order(self, order: jax.Array) -> jax.Array:
        """The inverse of a row-sharded GLOBAL-position order, row-sharded
        global positions again.  The order keeps every shard's rows in
        its own block (see permute_rows), so each shard inverts its own
        block: no sort of the whole order on one device."""
        if self._inverse is None:
            def body(o):
                base = jax.lax.axis_index(DATA_AXIS) * o.shape[-1]
                return base + jnp.argsort(o - base).astype(o.dtype)
            self._inverse = jax.jit(shard_map(
                body, mesh=self.mesh, in_specs=P(DATA_AXIS),
                out_specs=P(DATA_AXIS)))
        return self._inverse(order)

    def shard_row_counts(self, mask: np.ndarray, n_pad: int) -> np.ndarray:
        """Per-LOCAL-shard True counts of a host row mask (file/layout
        order, padded to this process's n_pad rows) — the bag-compaction
        window overflow check (models/gbdt.py).  Shard membership is
        position-fixed (every device-side re-sort, including the
        in-bag-first arrangement, is shard-local), so the static
        contiguous blocks of the padded layout ARE the shards."""
        m = np.asarray(mask, dtype=bool)
        if m.shape[-1] < n_pad:
            m = np.pad(m, (0, n_pad - m.shape[-1]))
        local = self.local_shard_count()
        return m.reshape(local, n_pad // local).sum(axis=1)

    # -- multi-host helpers (jax.process_count() > 1) -------------------
    def replicate(self, arr) -> jax.Array:
        """Host array (identical on every process) -> replicated global."""
        return _put_sharded(np.asarray(arr), self.mesh, P())

    def local_rows(self, garr: jax.Array) -> jax.Array:
        """This process's contiguous row block of a P(..., DATA_AXIS)-
        sharded global array, as a process-local array.  The per-device
        shards are committed to different local devices, so they
        concatenate on the host (one local-size copy per call)."""
        if jax.process_count() == 1:
            return garr
        pos = {d: i for i, d in enumerate(self.mesh.devices.flat)}
        shards = sorted(garr.addressable_shards, key=lambda s: pos[s.device])
        return jnp.asarray(np.concatenate([np.asarray(s.data)
                                           for s in shards], axis=-1))

    def replicated_to_local(self, tree):
        """Fully-replicated global tree arrays -> process-local arrays so
        they compose with local score/valid tensors."""
        if jax.process_count() == 1:
            return tree
        return jax.tree_util.tree_map(
            lambda a: jnp.asarray(a.addressable_data(0)), tree)


class FeatureShardedGrower:
    """Grows trees with FEATURES sharded over the mesh (tree_learner=
    feature).

    TPU-native equivalent of FeatureParallelTreeLearner (reference
    src/treelearner/feature_parallel_tree_learner.cpp): every device holds
    all rows (grad/hess/bag replicated), the [F, N] bin matrix is split
    along F, each shard scans best splits only for its features, and an
    all-gather + deterministic argmax replaces Allreduce(MaxReducer).
    The reference's greedy bin-count load balancing (:26-43) is unneeded:
    shards carry equal feature counts and the scan is vectorized.
    """

    def __init__(self, mesh: Mesh, *, max_leaves: int, max_bin: int,
                 params: SplitParams, max_depth: int = -1,
                 row_chunk: int = 0, hist_impl: str = "xla"):
        self.mesh = mesh
        self.num_shards = mesh.devices.size
        kw = dict(max_leaves=max_leaves, max_bin=max_bin, params=params,
                  max_depth=max_depth, row_chunk=row_chunk,
                  feature_axis=FEATURE_AXIS, hist_impl=hist_impl)
        self._grow = _sharded_grow_fn(
            mesh, kw,
            in_specs=(P(FEATURE_AXIS, None), P(None), P(None),
                      P(None), P(FEATURE_AXIS)),
            leaf_id_spec=P(None))

    def padded_features(self, f: int) -> int:
        return padded_size(f, self.num_shards)

    def _put_feature_sharded(self, arr: np.ndarray) -> jax.Array:
        """Place an array split on its FIRST (feature) axis.

        Multi-host (the reference's multi-machine
        FeatureParallelTreeLearner: every machine holds ALL rows and a
        feature slice, feature_parallel_tree_learner.cpp:45-78): each
        process passes the IDENTICAL full array (all machines loaded the
        whole file) and contributes the slices its own devices own —
        assembled with make_array_from_process_local_data without any
        cross-host copy."""
        spec = P(*([FEATURE_AXIS] + [None] * (arr.ndim - 1)))
        sharding = NamedSharding(self.mesh, spec)
        if jax.process_count() == 1:
            return jax.device_put(arr, sharding)
        chunk = arr.shape[0] // self.num_shards
        pos = {d: i for i, d in enumerate(self.mesh.devices.flat)}
        mine = sorted((d for d in self.mesh.devices.flat
                       if d.process_index == jax.process_index()),
                      key=lambda d: pos[d])
        local = np.concatenate([arr[pos[d] * chunk:(pos[d] + 1) * chunk]
                                for d in mine])
        return jax.make_array_from_process_local_data(sharding, local,
                                                      arr.shape)

    def shard_bins(self, bins: np.ndarray) -> jax.Array:
        """Pad F to a multiple of the shard count (padded features have
        all-zero bins and a False feature_mask) and place split on F."""
        f, n = bins.shape
        pad = self.padded_features(f) - f
        if pad:
            bins = np.pad(bins, ((0, pad), (0, 0)))
        return self._put_feature_sharded(bins)

    def shard_rows(self, arr: np.ndarray, n_pad: int, fill=0) -> jax.Array:
        """Rows are replicated under feature parallelism; pad and place
        (multi-host: every process passes the identical full array)."""
        return _pad_rows_and_put(arr, n_pad, fill, self.mesh,
                                 P(*([None] * arr.ndim)))

    def replicate(self, arr) -> jax.Array:
        return _put_sharded(np.asarray(arr), self.mesh, P())

    def local_replicated(self, garr: jax.Array) -> jax.Array:
        """Replicated global array -> process-local array."""
        if jax.process_count() == 1:
            return garr
        return jnp.asarray(garr.addressable_data(0))

    def replicated_to_local(self, tree):
        if jax.process_count() == 1:
            return tree
        return jax.tree_util.tree_map(
            lambda a: jnp.asarray(a.addressable_data(0)), tree)

    def grow(self, bins_dev, grad, hess, bag_mask, feature_mask):
        fmask = np.asarray(feature_mask)
        pad = self.padded_features(len(fmask)) - len(fmask)
        if pad:
            fmask = np.pad(fmask, (0, pad))
        fmask = self._put_feature_sharded(fmask)
        return self._grow(bins_dev, grad, hess, bag_mask, fmask)
