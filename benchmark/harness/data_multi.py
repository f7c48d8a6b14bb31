"""The class-wise cells' generator: a configuration's `data` block -> binned
rows of images and their classes.

Like `data.py`, the POPULATION is the configuration's and `--seed` gives
the ORDER.  From `population_seed`: one prototype a class (the ink
probability of each pixel of a `side` x `side` image, a few Gaussian
strokes inside the image's central box), then ONE block of `block_rows`
distinct images, each a prototype shifted by up to `shift` pixels and
thickened by a factor of its own, inked pixel by pixel (a pixel is 0 where
it draws no ink; where it does, `saturated` of the time 255, else anything
from 1 to 254), every pixel at least `ink_floor` likely, so that no column
is all zeros.  Each column is binned into at most `max_bin`
equal-population bins (`data.equal_population_bounds`), the binned block
tiled to `num_data` rows.  The label model works over pixel regions: a
class's logit is `sharpness` x the dot product of the image's ink in
`regions` x `regions` squares with that class's prototype there, centred,
less a bias a class that evens the classes out; every row draws its label
from its image's softmax with noise of its own,
so no two rows share a gradient history.  `--seed` permutes the block's
rows, the whole tiles among themselves and the last, partial tile, as
`data.py` does.  The program receives only the arrays.
"""

from __future__ import annotations

import numpy as np

from harness.data import Rows, equal_population_bounds


def prototypes(rng: np.random.Generator, img: dict, classes: int):
    """[classes, side * side] ink probabilities, each a few strokes."""
    side = int(img["side"])
    yy, xx = np.mgrid[0:side, 0:side].astype(np.float64)
    lo, hi = img["box"]
    out = np.zeros((classes, side, side))
    for c in range(classes):
        for _ in range(int(img["strokes"])):
            # a stroke: Gaussian ink along a segment between two points
            (y0, x0), (y1, x1) = rng.uniform(lo, hi, (2, 2))
            t = np.linspace(0.0, 1.0, 12)
            for y, x in zip(y0 + t * (y1 - y0), x0 + t * (x1 - x0)):
                out[c] = np.maximum(out[c], np.exp(
                    -((yy - y) ** 2 + (xx - x) ** 2)
                    / (2.0 * img["width"] ** 2)))
    return out.reshape(classes, side * side)


def _shifted(protos: np.ndarray, side: int, shift: int) -> np.ndarray:
    """[classes, (2 shift + 1)^2, side * side]: every class's prototype
    moved by each offset, what leaves the image lost and zeros coming in."""
    grid = protos.reshape(-1, side, side)
    pad = np.pad(grid, ((0, 0), (shift, shift), (shift, shift)))
    offs = range(2 * shift + 1)
    return np.stack([pad[:, dy:dy + side, dx:dx + side]
                     for dy in offs for dx in offs], 1).reshape(
                         grid.shape[0], -1, side * side)


def make_rows(data: dict, num_data: int, max_bin: int, seed: int) -> Rows:
    rng = np.random.default_rng([int(data["population_seed"]), 0x6D6E6973])
    img, lab = data["image"], data["label"]
    side, classes = int(img["side"]), int(data["classes"])
    block = int(min(data["block_rows"], num_data))
    protos = prototypes(rng, img, classes)
    moved = _shifted(protos, side, int(img["shift"]))

    latent = rng.integers(0, classes, block)
    where = rng.integers(0, moved.shape[1], block)
    thick = rng.lognormal(0.0, img["thickness_sigma"], block)[:, None]
    ink_p = np.clip(moved[latent, where] * thick * img["ink_scale"],
                    img["ink_floor"], img["ink_cap"]).astype(np.float32)
    inked = rng.random(ink_p.shape, dtype=np.float32) < ink_p
    value = np.where(rng.random(ink_p.shape, dtype=np.float32)
                     < img["saturated"], 255,
                     rng.integers(1, 255, ink_p.shape)).astype(np.float32)
    pixels = np.where(inked, value, 0.0)                  # [block, F]
    del ink_p, value

    # the label model: ink over regions against each class's prototype
    r = int(lab["regions"])
    cell = side // r

    def regions(ink: np.ndarray) -> np.ndarray:
        grid = ink.reshape(-1, side, side)[:, :r * cell, :r * cell]
        return grid.reshape(-1, r, cell, r, cell).mean((2, 4)).reshape(
            -1, r * r)
    mine = regions(inked.astype(np.float32))
    want = regions(protos.astype(np.float32))
    mine -= mine.mean(1, keepdims=True)
    want -= want.mean(1, keepdims=True)
    logit = np.float32(lab["sharpness"]) * mine @ want.T  # [block, classes]
    for _ in range(30):     # a bias a class, so that classes are about even
        logit -= logit.max(1, keepdims=True)
        p = np.exp(logit)
        p /= p.sum(1, keepdims=True)
        logit -= np.log(p.mean(0) * classes)
    cdf = np.cumsum(p, 1).astype(np.float32)
    del inked, mine

    bounds, cols = [], []
    for f in range(pixels.shape[1]):
        b = equal_population_bounds(pixels[:, f], max_bin)
        bounds.append(b)
        cols.append(np.searchsorted(b, pixels[:, f], side="left")
                    .astype(np.uint8))
    block_bins = np.stack(cols)
    del pixels, cols

    whole, rest = divmod(num_data, block)
    # a label from the image's softmax: fresh noise for every row of every
    # tile
    u = rng.random(num_data, dtype=np.float32)
    label = np.empty(num_data, np.float32)
    bins = np.empty((block_bins.shape[0], num_data), np.uint8)

    def draw(lo: int, rows: np.ndarray, noise: np.ndarray) -> None:
        label[lo:lo + len(rows)] = np.minimum(
            (noise[:, None] > cdf[rows]).sum(1), classes - 1)

    order = np.random.default_rng([int(seed), 0x6F726472])
    rows_in = order.permutation(block)
    tiles = order.permutation(whole)
    shuffled = block_bins[:, rows_in]
    for at, tile in enumerate(tiles):
        lo = at * block
        bins[:, lo:lo + block] = shuffled
        draw(lo, rows_in, u[tile * block:(tile + 1) * block][rows_in])
    if rest:
        last = order.permutation(rest)
        bins[:, whole * block:] = block_bins[:, :rest][:, last]
        draw(whole * block, last, u[whole * block:][last])
    return Rows(bins=bins, upper_bounds=bounds, label=label)
