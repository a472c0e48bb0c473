"""Pallas TPU kernels for XDMA hot paths.

Every ``pallas_call`` in the repo takes its ``interpret`` flag from
:func:`interpret_mode`, so there is one rule for the whole library: compiled
Mosaic kernels on a TPU, the Pallas interpreter on the CPU backend (how the
test suite runs), and an error on any other platform.
"""
import contextlib
from typing import Iterator, Optional

import jax

_FORCED: Optional[bool] = None


def interpret_mode() -> bool:
    """A kernel's ``interpret`` flag, decided from the backend it is traced
    for: ``False`` on ``tpu``, ``True`` on ``cpu``.  Any other platform
    raises rather than silently picking a mode.  Inside
    :func:`forced_interpret` the forced flag wins."""
    if _FORCED is not None:
        return _FORCED
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"no Pallas lowering for platform {platform!r}: "
                       "kernels compile for 'tpu' and interpret on 'cpu'")


@contextlib.contextmanager
def forced_interpret(interpret: bool) -> Iterator[None]:
    """Trace every kernel built inside the block with ``interpret``.  Compile
    tests force ``False`` to lower for a described TPU from a CPU process."""
    global _FORCED
    was, _FORCED = _FORCED, bool(interpret)
    try:
        yield
    finally:
        _FORCED = was


from . import ops, ref  # noqa: E402,F401  (submodules import interpret_mode)
