"""The one platform decision behind every Pallas kernel in ``ops/``.

Four config switches (``rollout_obs_kernel``, ``rollout_env_kernel``,
``lob_match_kernel``, ``data_compress``) choose ``off|on|interpret``;
the kernels' own entry points take ``interpret=None``.  Both resolve
here and nowhere else:

  * ``off``        the plain-XLA twin (the parity oracle), everywhere;
  * ``interpret``  the Pallas interpreter, everywhere (CPU parity tests);
  * ``on``         on a TPU: the compiled Mosaic kernel — whatever the
                   compiler or the kernel raises is raised, there is no
                   shape gate or ``try/except`` behind which the twin or
                   the interpreter could stand in.  Off the TPU there is
                   no Mosaic compiler, and ``on`` is the XLA twin (what
                   the CPU suite runs).
"""
from __future__ import annotations

from typing import Optional

import jax

KERNEL_MODES = ("off", "on", "interpret")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret=None`` at a kernel entry point: compiled on a TPU,
    the Pallas interpreter elsewhere."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def kernel_interpret(mode: str) -> Optional[bool]:
    """Resolve an ``off|on|interpret`` switch to the ``interpret``
    argument of its kernel, or ``None`` for the plain-XLA twin."""
    if mode == "interpret":
        return True
    if mode == "on":
        return False if on_tpu() else None
    if mode == "off":
        return None
    raise ValueError(f"kernel mode must be one of {KERNEL_MODES}, got {mode!r}")
