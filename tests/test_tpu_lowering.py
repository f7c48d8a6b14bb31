"""Cross-lowering of the Pallas histogram kernels for the TPU, from this
CPU host: `fn.trace(...).lower(lowering_platforms=("tpu",))` runs the
Pallas -> Mosaic front end (not the Mosaic back end) and needs no chip.

It catches the class of breakage that shipped once already: a kernel
that only ever ran interpreted and that the TPU lowering refuses
(unimplemented primitives, block shapes the lowering rejects).  What
only a chip can say — the Mosaic back end, VMEM, numerics — is
chip_smoke.py's job.
"""

import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.models.gbdt import resolve_hist_fused
from lightgbm_tpu.ops import hist_pallas as hp
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.utils.log import LightGBMError

S = jax.ShapeDtypeStruct
N = 8 * hp.PALLAS_ROW_BLOCK
I32 = S((), jnp.int32)
F32 = S((), jnp.float32)


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


# every Pallas kernel the default training path reaches (hist_fused=auto
# -> two-op, hist_acc=f32): the masked full sweep and the ordered-
# partition block-list sweep (and the ranged sweep, the maintained
# contiguous-range API), at one / two / three / nine feature blocks.
# F = 13, 28 and 39 do not divide their feature block (16): the last
# block runs past the array (13: ONE block larger than the array; 39: a
# ragged third), and the kernels read the matrix in place — no wrapper
# pads it.  F = 8 and 48 divide their block and never had a pad: the
# cases nothing changes for.
@pytest.mark.parametrize("max_bin", [63, 255])
@pytest.mark.parametrize("f", [8, 13, 28, 39, 48, 136])
def test_default_path_kernels_lower_for_tpu(f, max_bin):
    nblocks = N // hp.PALLAS_ROW_BLOCK
    for fn, more, statics in (
            (hp.leaf_histogram_masked, (), {}),
            (hp.leaf_histogram_ranged, (I32, I32), {}),
            (hp.leaf_histogram_blocklist, (S((nblocks,), jnp.int32), I32),
             {"grid_blocks": 8})):
        low = _lower_for_tpu(fn, _sweep_args(f) + more, max_bin=max_bin,
                             **statics)
        assert "tpu_custom_call" in low.as_text(), fn
        assert not _pads_of_u8(low), (fn, _pads_of_u8(low))


@pytest.mark.parametrize("f", [16, 28])
def test_fused_kernels_are_refused_today(f):
    """Pins the refusal that makes hist_fused=auto resolve to the two-op
    path (models/gbdt.py resolve_hist_fused).  If this test starts
    failing the fused kernels lower again: flip `auto` back on for
    non-CPU platforms there, and extend the test above to cover them."""
    params = SplitParams(100, 1e-3, 0.0, 0.0, 0.0)
    stats = (I32, F32, F32)
    tail = (S((f, 255, 3), jnp.float32), S((f,), jnp.bool_), stats, stats)
    with pytest.raises((NotImplementedError, ValueError)):
        _lower_for_tpu(hp.leaf_histogram_masked_fused,
                       _sweep_args(f) + tail, max_bin=255, params=params)
    with pytest.raises((NotImplementedError, ValueError)):
        _lower_for_tpu(
            hp.leaf_histogram_blocklist_fused,
            _sweep_args(f) + (S((N // hp.PALLAS_ROW_BLOCK,), jnp.int32),
                              I32) + tail,
            max_bin=255, params=params, grid_blocks=8)


def test_hist_fused_rule():
    """auto is the two-op path everywhere; on runs only where the
    kernels interpret (CPU) and is fatal on any other platform."""
    for platform in ("cpu", "tpu"):
        assert resolve_hist_fused("auto", "pallas", platform) is False
        assert resolve_hist_fused("off", "pallas", platform) is False
        assert resolve_hist_fused("auto", "xla", platform) is False
    assert resolve_hist_fused("on", "pallas", "cpu") is True
    with pytest.raises(LightGBMError, match="do not lower for "
                                            "platform=tpu"):
        resolve_hist_fused("on", "pallas", "tpu")
    with pytest.raises(LightGBMError, match="requires the Pallas"):
        resolve_hist_fused("on", "xla", "cpu")
