"""JAX-free native fast path for `task=predict`.

The reference serves prediction from one warm process: TextReader blocks
feed an OpenMP loop that parses, descends the trees and formats each row
(src/application/predictor.hpp:82-130).  The framework's default predict
path pays costs the reference never sees — Python+JAX import, device
upload, device readback.  This module is the equivalent warm
loop: the model text is parsed host-side (no jax import anywhere on this
path), flattened into contiguous arrays, and each input chunk runs one
fused native parse -> descend -> transform -> "%g" pass
(native.predict_chunk / ingest.cpp lgt_predict_*_mt), streaming to the
output file with bounded memory.

Output is byte-identical to the default path (and to the reference
binary): same Atof parse arithmetic, same `<= threshold` descent, same
double accumulation order, same sigmoid/softmax expressions, same "%g"
formatting.  test_predict_fast pins fast-vs-default identity across
formats and modes; test_e2e_parity's golden predict tests run through
this path via the CLI.

Returns False from try_fast_predict when the native library is
unavailable so cli.Application falls back to the JAX path.
"""

from __future__ import annotations

__jax_free__ = True

from typing import List, Optional, Tuple

import numpy as np

from .analysis.contracts import contract
from .config import Config
from .io.parser import sniff_format
from .models.tree import Tree, parse_model_text
from .utils import log

# Input chunk size: large enough to amortize thread spawn per chunk,
# small enough to bound memory for arbitrarily large inputs.
CHUNK_BYTES = 64 << 20


def format_pred_rows(res: "np.ndarray", leaf: bool) -> bytes:
    """Predict results -> output bytes, the ONE home of the prediction
    output format (Predictor::SaveTextPredictionsToFile role), shared by
    cli.predict's streaming blocks and the serving subsystem so the two
    cannot drift: leaf mode tab-joins integer leaf ids per row; score
    mode is bulk native "%g" (byte-identical to Python's "%g" for
    finite doubles) with the Python loop as the no-toolchain fallback.

    res: [N, T] leaf indices when leaf, else [K, N] scores.  0-row
    input returns b"" (the serving 0-row contract; cli blocks are never
    empty)."""
    if leaf:
        if res.shape[0] == 0:
            return b""
        return ("\n".join(
            "\t".join(str(int(v)) for v in row) for row in res)
            + "\n").encode()
    if res.shape[1] == 0:
        return b""
    from . import native
    rows = np.ascontiguousarray(res.T)               # [N, K]
    blob = native.format_g(rows)
    if blob is not None:
        return blob
    return ("\n".join(
        "\t".join("%g" % v for v in res[:, i])
        for i in range(res.shape[1])) + "\n").encode()


class _LightModel:
    """Model-text header + trees, parsed without models.gbdt (which
    imports jax).  The actual reader is models.tree.parse_model_text,
    shared with GBDT.load_model_from_string so the two paths cannot
    drift; sigmoid defaults like cli.init_predict's prediction-only
    GBDT (no binary objective configured -> -1)."""

    def __init__(self, model_str: str):
        header, trees = parse_model_text(model_str)
        self.num_class = header["num_class"]
        self.label_idx = header["label_index"]
        self.max_feature_idx = header["max_feature_idx"]
        self.sigmoid = (header["sigmoid"]
                        if header["sigmoid"] is not None else -1.0)
        self.trees: List[Tree] = trees

    def used_trees(self, num_model_predict: int) -> List[Tree]:
        """cli.init_predict's set_num_used_model call, resolved
        (models.tree.select_used_trees, shared with serving)."""
        from .models.tree import select_used_trees
        return select_used_trees(self.trees, self.num_class,
                                 num_model_predict)


def _read_chunks(path: str, has_header: bool):
    """Yield line-aligned byte chunks of the input file, skipping the
    first NON-blank line when has_header (matching io/dataset
    _skip_header and cli.predict's blocks()).

    The header skip runs BEFORE chunking starts and carries the partial
    header across reads explicitly, so a header line longer than
    CHUNK_BYTES (or preceded by blank lines) can never truncate data:
    the old interleaved skip left that guarantee implicit in the
    chunk-boundary handling (test_predict_fast pins the regression)."""
    with open(path, "rb") as f:
        carry = b""
        skip = has_header
        while skip:
            block = f.read(CHUNK_BYTES)
            if not block:
                return  # whole file is the header (or blanks): no rows
            carry += block
            pos = 0
            while True:
                eol = carry.find(b"\n", pos)
                if eol < 0:
                    # header (or leading blanks) continue into the next
                    # read: keep the partial line as the carry
                    carry = carry[pos:]
                    break
                if carry[pos:eol].strip(b"\r"):
                    carry = carry[eol + 1:]   # past the header line
                    skip = False
                    break
                pos = eol + 1                 # blank line: keep looking
        while True:
            block = f.read(CHUNK_BYTES)
            if not block:
                break
            buf = carry + block
            cut = buf.rfind(b"\n")
            if cut < 0:
                carry = buf
                continue
            chunk, carry = buf[:cut + 1], buf[cut + 1:]
            yield chunk
        if carry.strip(b"\r\n"):
            yield carry


# bytes per _sniff_format read; the sniff keeps reading past this until
# it has complete data lines (a header alone can exceed one read)
SNIFF_BYTES = 1 << 20


def _sniff_format(path: str, has_header: bool) -> Tuple[str, str]:
    """(fmt, sep) from the first data lines (Parser::CreateParser role),
    via the shared complete-lines sniff (io/parser.sniff_format — also
    the serving request sniff, so the two paths cannot drift)."""
    with open(path, "rb") as f:
        return sniff_format(lambda: f.read(SNIFF_BYTES), has_header)


@contract.jax_free
@contract.rank_uniform
def try_fast_predict(cfg: Config) -> bool:
    """Run task=predict through the native path; False -> caller falls
    back to the default JAX path (native toolchain unavailable).

    @contract.jax_free: the whole point of this path is the reference
    binary's process-startup profile — graftcheck GC002 verifies
    nothing it transitively calls imports jax, even lazily.
    @contract.rank_uniform: the decision derives from config (task,
    modes, native-engine availability) and the shared input model
    artifact — identical on every rank of a fleet, so graftsync's
    GC009 accepts the CLI's fast-path early exit ahead of the
    jax-path fallback (whose booster init allgathers under
    multi-host)."""
    from . import native
    if native.get_lib() is None:
        return False
    if not cfg.input_model:
        log.fatal("Need a model file for prediction (input_model)")
    log.info("Started prediction...")
    with open(cfg.input_model) as f:
        model = _LightModel(f.read())
    trees = model.used_trees(cfg.num_model_predict)
    forest = native.ForestSpec(trees, model.num_class, model.sigmoid)
    mode = (2 if cfg.is_predict_leaf_index
            else 1 if cfg.is_predict_raw_score else 0)
    num_feat = model.max_feature_idx + 1
    fmt, sep = _sniff_format(cfg.data, cfg.has_header)

    # pull the first chunk BEFORE opening (truncating) the output file so
    # an empty input fatals without clobbering a previous result (same
    # no-clobber contract as cli.predict)
    gen = _read_chunks(cfg.data, cfg.has_header)
    first: Optional[bytes] = None
    row0 = 0
    for chunk in gen:
        got = native.predict_chunk(chunk, fmt, sep, model.label_idx,
                                   num_feat, forest, mode, row0=row0)
        if got is None:
            return False  # native refused (capacity edge): slow path
        blob, rows = got
        row0 += rows
        if blob:
            first = blob
            break
    if first is None:
        log.fatal("Data file %s is empty" % cfg.data)
    from .resilience.atomic import atomic_writer
    with atomic_writer(cfg.output_result) as out_f:
        out_f.write(first)
        for chunk in gen:
            got = native.predict_chunk(chunk, fmt, sep, model.label_idx,
                                       num_feat, forest, mode, row0=row0)
            if got is None:
                # mid-file native refusal: finishing through two paths
                # would interleave buffers — fatal rather than corrupt
                log.fatal("Native predict failed mid-file on %s" % cfg.data)
            blob, rows = got
            row0 += rows
            out_f.write(blob)
    log.info("Finished prediction, results saved to %s" % cfg.output_result)
    return True
