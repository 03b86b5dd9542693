"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

Every kernel and every schedule-compiler entry takes ``interpret=None``
and resolves it here, so no caller has to pick the mode and none can
forget to: on a TPU backend the kernels always compile, and on any other
backend (the CPU test runs) they run in the Pallas interpreter.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` picks the mode from ``jax.default_backend()``: compiled
    (``False``) on a TPU, interpreted anywhere else. An explicit bool is
    kept as given, so a compile for a described TPU from a CPU host can
    still ask for ``interpret=False``."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
