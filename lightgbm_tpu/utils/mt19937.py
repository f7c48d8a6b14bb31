"""Bit-exact replica of the reference's seeded RNG.

The reference (include/LightGBM/utils/random.h) wraps std::mt19937 with
libstdc++'s uniform_real_distribution<double>(0,1) and a sequential
selection-sampling `Sample(N, K)`.  Bagging (src/boosting/gbdt.cpp:109-160)
and feature_fraction (src/treelearner/serial_tree_learner.cpp:140-147) only
ever consume NextDouble(), so reproducing that stream bit-exactly lets our
tree-identity / trajectory-parity tests run with bagging enabled.

Verified against a g++ probe: NextDouble == (x1 + x2*2^32) / 2^64 with two
raw 32-bit draws x1, x2 (libstdc++ generate_canonical<double, 53> with
mt19937).  The words are numpy's own MT19937 bit generator's, seeded as
std::mt19937(seed) seeds itself (init_genrand): the same recurrence in C,
11 ms for 2M words where the twist in numpy expressions (until PR 33) took
0.4 s.
"""

from __future__ import annotations

__jax_free__ = True

import numpy as np

_N = 624
_TWO32 = 4294967296.0


def _seed_state(seed: int) -> np.ndarray:
    s = np.empty(_N, dtype=np.uint64)
    s[0] = np.uint64(seed & 0xFFFFFFFF)
    for i in range(1, _N):
        prev = s[i - 1]
        s[i] = (np.uint64(1812433253) * (prev ^ (prev >> np.uint64(30))) + np.uint64(i)) & np.uint64(0xFFFFFFFF)
    return s.astype(np.uint32)


def _temper(words: np.ndarray) -> np.ndarray:
    """The output transform of MT19937 over raw state words."""
    out = words.astype(np.uint32)
    out ^= out >> np.uint32(11)
    out ^= (out << np.uint32(7)) & np.uint32(0x9D2C5680)
    out ^= (out << np.uint32(15)) & np.uint32(0xEFC60000)
    out ^= out >> np.uint32(18)
    return out


class Mt19937Random:
    """Replica of LightGBM::Random (reference include/LightGBM/utils/random.h:14-75)."""

    # doubles made a chunk at a time: 16 MB of raw words, never 2 x count
    _CHUNK = 1 << 20

    def __init__(self, seed: int):
        self._bg = np.random.MT19937()  # graftlint: disable=GL005 -- the bit generator alone, its state set to the reference's seeding: this IS the reference's stream
        self._load(_seed_state(seed), _N)

    def _load(self, key: np.ndarray, pos: int) -> None:
        """key: the 624 words after the last twist; pos: the next one to
        temper (624 = twist first), as numpy and libstdc++ hold them."""
        self._bg.state = {"bit_generator": "MT19937",
                          "state": {"key": key, "pos": int(pos)}}

    def _raw(self, count: int) -> np.ndarray:
        return self._bg.random_raw(count).astype(np.uint32)

    def get_state(self) -> np.ndarray:
        """Serializable stream state: generator state + the undrawn words
        of its last twist (checkpointing; see GBDT.save_checkpoint)."""
        st = self._bg.state["state"]
        key = st["key"].astype(np.uint32)
        return np.concatenate([np.asarray([_N], dtype=np.uint32), key,
                               _temper(key[int(st["pos"]):])])

    def set_state(self, packed: np.ndarray) -> None:
        packed = np.asarray(packed, dtype=np.uint32)
        n = int(packed[0])
        undrawn = len(packed) - 1 - n
        if n != _N or undrawn > _N:
            raise ValueError("not a Mt19937Random state: %d state words, "
                             "%d undrawn" % (n, undrawn))
        self._load(packed[1:1 + n].copy(), _N - undrawn)

    def next_doubles(self, count: int) -> np.ndarray:
        """count draws of uniform_real_distribution<double>(0,1): 2 raws
        each, (x1 + x2 * 2^32) / 2^64 rounded once to a double."""
        out = np.empty(count, dtype=np.float64)
        for lo in range(0, count, self._CHUNK):
            raw = self._bg.random_raw(2 * min(self._CHUNK, count - lo))
            # x1 + x2 * 2^32 is exact in 64 bits; the cast rounds it to
            # nearest even as the reference's float64 add does
            out[lo:lo + len(raw) // 2] = raw[0::2] | (raw[1::2]
                                                      << np.uint64(32))
        out *= 1.0 / (_TWO32 * _TWO32)
        return out

    def next_double(self) -> float:
        return float(self.next_doubles(1)[0])

    def next_ints(self, upper_bounds: np.ndarray) -> np.ndarray:
        """Sequential NextInt(0, ub) draws, one per entry of upper_bounds
        (reference random.h:30-40: libstdc++ uniform_int_distribution with
        a fresh distribution per call).

        libstdc++ (GCC >= 11, including the g++ 12 that builds the
        reference binary here) downscales a 32-bit urng with Lemire's
        multiply-shift (bits/uniform_int_dist.h _S_nd, "Fast Random
        Integer Generation in an Interval"): product = raw * ub;
        accept unless low32(product) < (2^32 - ub) % ub (redraw on
        reject); result = product >> 32.  Rejections consume extra raws,
        shifting every later draw, so the vectorized replay realigns the
        draw->call mapping to a fixpoint (rejections are rare: the
        rejected band is < ub/2^32 of the space).
        """
        ubs = np.asarray(upper_bounds, dtype=np.uint64)
        k = len(ubs)
        out = np.empty(k, dtype=np.int64)
        two32 = 1 << 32
        threshold = ((np.uint64(two32) - ubs) % ubs).astype(np.uint64)
        filled = 0
        while filled < k:
            m = k - filled
            draws = self._raw(m).astype(np.uint64)
            # map draw position -> call index: a rejected draw repeats
            # its call, so call[p] = filled + (# accepted before p).
            # thresholds vary slowly across calls, so iterate to fixpoint.
            def acc_of(call):
                prod = draws * ubs[call]
                low = prod & np.uint64(0xFFFFFFFF)
                return low >= threshold[call], prod

            acc, _ = acc_of(np.minimum(filled + np.arange(m), k - 1))
            for _ in range(64):
                call = filled + np.concatenate(
                    [[0], np.cumsum(acc[:-1])]).astype(np.int64)
                call = np.minimum(call, k - 1)
                new_acc, prod = acc_of(call)
                if np.array_equal(new_acc, acc):
                    break
                acc = new_acc
            else:   # pathological oscillation: scalar replay of this batch
                for d in draws:
                    if filled >= k:
                        break
                    p = int(d) * int(ubs[filled])
                    if (p & 0xFFFFFFFF) >= int(threshold[filled]):
                        out[filled] = p >> 32
                        filled += 1
                continue
            good = acc & (call < k)
            out[call[good]] = (prod[good] >> np.uint64(32)).astype(np.int64)
            filled += int(np.count_nonzero(good))
        return out

    def _selection_mask(self, n: int, k: int,
                        out: np.ndarray = None) -> np.ndarray:
        """Acceptance mask of sequential selection sampling over exactly n
        NextDouble draws: accept i when draw_i < (k - taken_i) / (n - i),
        written into `out` (bool[n], contiguous) where one is given.

        The walk is inherently sequential (taken_i depends on every
        earlier accept), so draws and walk run as ONE pass in the native
        layer (lgt_mt_selection_mask, continued from this stream's raw
        state: no array of draws, under a second for 68M rows); without a
        toolchain the draws are made here and walked by
        native.selection_walk's Python fallback.
        """
        from .. import native
        if out is None:
            out = np.empty(n, dtype=bool)
        st = self._bg.state["state"]
        key = np.ascontiguousarray(st["key"], dtype=np.uint32)
        pos = native.mt_selection_mask(key, int(st["pos"]), n, k, out)
        if pos is None:
            out[:] = native.selection_walk(self.next_doubles(n), k)
        else:
            self._load(key, pos)
        return out

    def sample(self, n: int, k: int) -> np.ndarray:
        """Sequential selection sampling; reference random.h:55-67.

        Must consume exactly n NextDouble draws regardless of acceptance,
        and accept index i when draw < (k - taken) / (n - i).
        """
        if k > n or k < 0:
            return np.zeros(0, dtype=np.int32)
        return np.flatnonzero(self._selection_mask(n, k)).astype(np.int32)

    def split_mask(self, n: int, k: int, out: np.ndarray = None) -> np.ndarray:
        """Like sample() but returns the boolean acceptance mask over [0, n)
        (in `out`, bool[n] and contiguous, where one is given).

        Mirrors the in/out-of-bag partition loop of GBDT::Bagging
        (reference src/boosting/gbdt.cpp:118-129).
        """
        return self._selection_mask(n, k, out)
