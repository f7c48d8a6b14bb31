"""RNG parity: our numpy mt19937 must reproduce libstdc++'s
std::mt19937 + uniform_real_distribution<double>(0,1) streams bit-exactly
(values captured from a g++ probe of the reference's Random class)."""

import numpy as np
import pytest

from lightgbm_tpu.utils.mt19937 import Mt19937Random, _seed_state

# first 8 NextDouble draws, seed 3 (bagging_seed default)
SEED3_DOUBLES = [
    0.070724880451056613, 0.83994904246836621, 0.12132857932963054,
    0.56931132579008759, 0.43706194029491091, 0.01874801048456996,
    0.040630737581659415, 0.24788830178027108,
]
# first 4, seed 2 (feature_fraction_seed default)
SEED2_DOUBLES = [
    0.18508208157401412, 0.93154086359448873, 0.94773061097358879,
    0.48474909631426499,
]
# raw 32-bit draws, seed 3
SEED3_RAW = [2365658986, 303761048, 3041471737, 3607553667]
# 2000th NextDouble, seed 3 (crosses several 624-word twist blocks)
SEED3_2000TH = 0.86037750863463835


def test_raw_draws():
    r = Mt19937Random(3)
    assert list(r._raw(4)) == SEED3_RAW


def test_next_doubles_seed3():
    r = Mt19937Random(3)
    np.testing.assert_array_equal(r.next_doubles(8), SEED3_DOUBLES)


def test_next_doubles_seed2():
    r = Mt19937Random(2)
    np.testing.assert_array_equal(r.next_doubles(4), SEED2_DOUBLES)


def test_block_boundary():
    r = Mt19937Random(3)
    assert r.next_doubles(2000)[-1] == SEED3_2000TH


def test_sample_consumes_n_draws():
    # Sample(N, K) must consume exactly N draws regardless of acceptances
    r1 = Mt19937Random(7)
    r1.sample(100, 10)
    after1 = r1.next_double()
    r2 = Mt19937Random(7)
    r2.next_doubles(100)
    after2 = r2.next_double()
    assert after1 == after2


def test_sample_matches_reference_algorithm():
    r = Mt19937Random(5)
    draws = Mt19937Random(5).next_doubles(50)
    got = r.sample(50, 12)
    taken = []
    for i in range(50):
        prob = (12 - len(taken)) / (50 - i)
        if draws[i] < prob:
            taken.append(i)
    assert list(got) == taken
    assert len(taken) == 12


# -- the fast draw against the replica it replaced ---------------------------
class _TwistInNumpy:
    """The generator as it stood until PR 33: the twist in numpy
    expressions, 624 words at a time (0.88 s for 2M doubles, 30 s for a bag
    of 68M rows), and the walk over an array of n draws.  Kept here as the
    oracle the fast draw is held to, word for word and mask for mask."""

    def __init__(self, seed):
        self.state = _seed_state(seed)
        self.buf = np.empty(0, np.uint32)

    def _twist(self):
        n, m = 624, 397
        a, upper, lower = (np.uint32(0x9908B0DF), np.uint32(0x80000000),
                           np.uint32(0x7FFFFFFF))
        s = self.state
        new = np.empty(n, np.uint32)
        y = (s & upper) | (np.roll(s, -1) & lower)
        mag = np.where((y & np.uint32(1)).astype(bool), a, np.uint32(0))
        new[:n - m] = s[m:] ^ (y[:n - m] >> np.uint32(1)) ^ mag[:n - m]
        step = n - m
        for lo in range(n - m, n - 1, step):
            hi = min(lo + step, n - 1)
            new[lo:hi] = (new[lo - step:hi - step] ^ (y[lo:hi] >> np.uint32(1))
                          ^ mag[lo:hi])
        y_last = (s[n - 1] & upper) | (new[0] & lower)
        new[n - 1] = (new[m - 1] ^ (y_last >> np.uint32(1))
                      ^ (a if (y_last & np.uint32(1)) else np.uint32(0)))
        out = new.copy()
        out ^= out >> np.uint32(11)
        out ^= (out << np.uint32(7)) & np.uint32(0x9D2C5680)
        out ^= (out << np.uint32(15)) & np.uint32(0xEFC60000)
        out ^= out >> np.uint32(18)
        self.state = new
        return out

    def raw(self, count):
        while len(self.buf) < count:
            self.buf = np.concatenate([self.buf, self._twist()])
        res, self.buf = self.buf[:count], self.buf[count:]
        return res

    def doubles(self, count):
        raw = self.raw(2 * count).astype(np.float64)
        return (raw[0::2] + raw[1::2] * 4294967296.0) / 4294967296.0 ** 2

    def mask(self, n, k):
        draws, taken = self.doubles(n), 0
        out = np.zeros(n, bool)
        for i in range(n):
            if draws[i] < (k - taken) / (n - i):
                out[i] = True
                taken += 1
        return out

    def packed(self):
        """What `get_state` gave: [624], the state, the undrawn words."""
        return np.concatenate([np.asarray([624], np.uint32), self.state,
                               self.buf])


SEEDS = [3, 2, 2 ** 32 - 1]


@pytest.mark.parametrize("seed", SEEDS)
def test_words_and_doubles_equal_the_numpy_twist(seed):
    old, new = _TwistInNumpy(seed), Mt19937Random(seed)
    for count in (1, 623, 624, 5000, 2, 1249):
        assert np.array_equal(old.raw(count), new._raw(count))
    for count in (1, 311, 2000, 7):
        assert np.array_equal(old.doubles(count), new.next_doubles(count))
    # more than one chunk of the doubles
    old, new = _TwistInNumpy(seed), Mt19937Random(seed)
    new._CHUNK = 1000
    assert np.array_equal(old.doubles(3500), new.next_doubles(3500))


@pytest.mark.parametrize("n,k", [(1000, 800), (39, 31), (20011, 16008),
                                 (7, 7), (5, 0)])
@pytest.mark.parametrize("seed", SEEDS)
def test_masks_equal_the_numpy_twist_across_a_state_round_trip(seed, n, k):
    """Bag after bag on ONE stream, the fast draw's state carried through
    `get_state` / `set_state` between them, and the old format's state
    accepted: what a checkpoint written before PR 33 holds."""
    old, new = _TwistInNumpy(seed), Mt19937Random(seed)
    assert np.array_equal(old.mask(n, k), new.split_mask(n, k))
    assert np.array_equal(old.packed(), new.get_state())
    resumed = Mt19937Random(0)
    resumed.set_state(new.get_state())
    from_old = Mt19937Random(0)
    from_old.set_state(old.packed())
    want = old.mask(n, k)
    assert want.sum() == k
    for rng in (new, resumed, from_old):
        assert np.array_equal(rng.split_mask(n, k), want)
        assert np.array_equal(rng.get_state(), old.packed())
    into = np.zeros(n + 5, bool)
    assert new.split_mask(n, k, out=into[:n]) is not None
    assert np.array_equal(into[:n], old.mask(n, k)) and not into[n:].any()


def test_walk_without_the_native_layer_is_the_same(monkeypatch):
    from lightgbm_tpu import native
    monkeypatch.setattr(native, "mt_selection_mask",
                        lambda *a, **k: None)
    old, new = _TwistInNumpy(3), Mt19937Random(3)
    assert np.array_equal(old.mask(5000, 4000), new.split_mask(5000, 4000))
    assert np.array_equal(old.doubles(10), new.next_doubles(10))


def test_set_state_refuses_what_is_no_state():
    with pytest.raises(ValueError):
        Mt19937Random(1).set_state(np.arange(700, dtype=np.uint32))
