"""Where Pallas kernels run: compiled for the TPU or in the interpreter.

Every kernel and every ``ops.py`` entry point takes ``interpret=None`` and
resolves it here, so the decision lives in one place: compiled (Mosaic)
on a TPU backend, the Pallas interpreter everywhere else. An explicit
``interpret=True``/``False`` always wins — CPU tests pin the interpreter,
and ahead-of-time compiles for a described TPU (whose process backend is
still the CPU) pin ``False``.
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else True unless the backend is a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
