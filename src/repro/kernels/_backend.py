"""Shared interpret-mode resolution for every Pallas kernel module.

Compiled Pallas kernels only make sense on a real TPU backend; everywhere
else (CPU CI, GPU hosts) the kernels run in interpret mode. Public kernel
entry points take ``interpret: bool | None = None`` and resolve ``None``
through :func:`_default_interpret` **before** entering jit, so the backend
probe never gets frozen into a jit cache (an earlier ``functools.cache``
on this function froze the first answer for the life of the process —
see PR 5's fix). Pass an explicit bool to override per call.
"""
from __future__ import annotations

import jax

__all__ = ["_default_interpret"]


def _default_interpret() -> bool:
    """True unless the **current** ``jax.default_backend()`` is TPU.

    Evaluated per call — it is one cached jax lookup — so a backend
    attached after the first call changes the answer. A failing backend
    probe raises: falling back to interpret mode would hide the device.
    """
    return jax.default_backend() != "tpu"
