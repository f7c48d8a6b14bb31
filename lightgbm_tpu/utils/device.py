"""Device resolution: the one place `device_type` becomes a JAX platform.

Everything that is about to touch a device calls
`resolve_device(config.device_type)` first — cli train and predict,
the Python API's Booster, and the serving forest's engine selection,
which covers task=serve, each serving front-end worker and a server
embedded through the API.

JAX's own platform selection hides a missing chip: with JAX_PLATFORMS
unset, a process that cannot get the TPU logs a warning and carries on
on the CPU backend, where every `auto` in this package resolves to its
slow side (XLA histograms, per-iteration dispatch, interpreted kernels,
no matmul serving route) and the run exits 0 looking like a slow TPU.
`device_type=tpu` therefore means a TPU or a fatal error, never a
fallback.  A chip belongs to one process: a second process asking for
it lands here and fails loudly instead of serving from the CPU.

Two steps, because multi-host training must run
`jax.distributed.initialize` between them (it refuses once a backend
is live, and `parallel/dist.init_distributed` reads `jax_platforms` to
choose the CPU collectives): `pin_platform` only sets configuration,
`resolve_device` pins and then initializes the backend to verify it.
"""

from __future__ import annotations

__jax_free__ = True   # at import; both functions import jax on call

from . import log

_logged = False


def pin_platform(device_type: str) -> None:
    """Apply `device_type=cpu` without initializing a backend.

    Must run before any JAX backend initializes; wins over a
    JAX_PLATFORMS environment setting.  Any other value leaves JAX's
    own selection alone (resolve_device checks what it selected)."""
    if device_type == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")


def resolve_device(device_type: str) -> str:
    """Pin `device_type`, initialize the backend and return its
    platform name.

    "" : whatever JAX selects.
    Fatal when a named device_type is not the platform that
    initialized.  Logs platform, device kind and device count once
    per process.  Under num_machines>1 call it AFTER
    init_distributed (pin_platform before)."""
    global _logged
    import jax
    pin_platform(device_type)
    devs = jax.devices()
    platform = devs[0].platform
    if not _logged:
        _logged = True
        log.info("Device: platform=%s device_kind=%s count=%d"
                 % (platform, devs[0].device_kind, len(devs)))
    if device_type and platform != device_type:
        log.fatal("device_type=%s but JAX initialized platform=%s "
                  "(device_kind=%s, %d device(s)): no such device is "
                  "attached, another process holds it (one process per "
                  "chip), or a backend was live before this call"
                  % (device_type, platform, devs[0].device_kind,
                     len(devs)))
    return platform


def memory_limit_bytes():
    """What the first device lets one process hold (`bytes_limit` of its
    `memory_stats()`), or None where the backend does not say (the CPU).
    What holds per-row state many times over sizes itself by it (DART's
    leaf bank, models/gbdt.py)."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return int(limit) if limit else None
