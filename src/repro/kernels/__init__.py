"""Pallas TPU kernels for the SOAR hot paths, and their route record.

Dispatchers that choose between a Pallas kernel and its XLA reference by
backend or by size call `note_route`, which logs the choice on the
`repro.kernels` logger (INFO, with `kernel` and `route` record fields), so
a caller can show which route each kernel took instead of switching in
silence. Routes: "mosaic"
(compiled Pallas kernel), "interpret" (Pallas interpreter, off-TPU) and
"xla" (the jnp reference). `interpret_mode` decides between the first two.
"""
from __future__ import annotations

import logging

import jax

_log = logging.getLogger(__name__)


def note_route(kernel: str, route: str) -> None:
    """Log one dispatch decision of `kernel` onto `route`."""
    _log.info("%s route: %s", kernel, route,
              extra={"kernel": kernel, "route": route})


def interpret_mode() -> bool:
    """The one interpret-mode rule every kernel dispatcher follows: Pallas
    compiles to Mosaic on a TPU backend and runs in the interpreter on any
    other. Call it as `kernels.interpret_mode()` so a test can steer it."""
    return jax.default_backend() != "tpu"
