"""Stage placement: which JAX backend a stage's kernels run on.

The accelerator is co-located with the process that drives it and belongs to
that process alone, so there is nothing to measure and nobody else to ask:
``device_placement="auto"`` runs every stage on the process's default
backend (the chip when JAX found one, the CPU under ``JAX_PLATFORMS=cpu``).
``"device"`` says the same thing explicitly; ``"host"`` pins a stage's task
threads to the CPU backend with ``jax.default_device`` — host-placed stages
run the *same* jitted kernels, so there is one code path.

Nothing here can turn a failure to reach the chip into a host placement:
``require_backend`` raises when JAX cannot initialise the platforms it was
told to use, and a forced ``"host"`` raises if the process has no CPU
backend to pin to.
"""

from __future__ import annotations

import contextlib


def require_backend():
    """Initialise this process's JAX backend now, in-process, and return its
    devices. A chip belongs to one process: whoever builds a Session is that
    process, so there is no probe to delegate and no fallback to take."""
    import jax

    try:
        return jax.devices()
    except RuntimeError as exc:
        raise RuntimeError(
            "blaze_tpu: no usable JAX device for platforms "
            f"{jax.config.jax_platforms or '(default order)'!r}: {exc}"
        ) from exc


def decide(conf) -> str:
    """Placement for one stage: "device" (the process's default backend)
    unless the configuration forces "host"."""
    from blaze_tpu.obs import attribution as _audit

    mode = conf.device_placement
    if mode == "host":
        _audit.note_placement("host", "conf_forced_host")
        return "host"
    if mode not in ("auto", "device"):
        raise ValueError(
            f"device_placement must be auto|device|host, got {mode!r}")
    _audit.note_placement("device", None)
    return "device"


@contextlib.contextmanager
def placed(decision: str):
    """Scope a task thread to the decided backend. "device" is the process
    default; "host" pins the CPU backend for this thread only."""
    import jax

    if decision != "host" or jax.default_backend() == "cpu":
        yield
        return
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        yield
