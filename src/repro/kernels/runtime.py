"""Kernel execution-policy helpers shared by every Pallas entry point."""
from __future__ import annotations

import jax


def resolve_interpret(value=None) -> bool:
    """Pallas interpret-mode resolution.

    ``None`` (the default, and ``ModelConfig.pallas_interpret``'s) runs
    compiled on a TPU backend and in the interpreter everywhere else.  An
    explicit ``True``/``False`` wins, except that interpret mode on a TPU
    backend raises: the Mosaic emulator there would serve every kernel
    from the host while reporting the chip as the device.
    """
    on_tpu = jax.default_backend() == "tpu"
    if value is None:
        return not on_tpu
    if value and on_tpu:
        raise ValueError(
            "Pallas interpret mode was requested on a TPU backend; the "
            "kernels run compiled there (leave pallas_interpret unset)")
    return bool(value)
