"""Shared kernel dispatch policy — one place deciding ref vs Pallas.

Every kernel package's ``ops.py`` used to repeat the ``use_pallas`` /
``interpret`` boilerplate with slightly different defaults.  ``resolve``
centralizes the policy:

* explicit booleans always win (tests force ``use_pallas=True,
  interpret=True`` to execute kernel bodies on CPU);
* ``None`` autodetects: the Pallas path turns on when the default JAX
  backend is a TPU, and interpret mode turns on everywhere else, so the
  same call site runs the hand-fused XLA reference on CPU hosts and the
  Mosaic-lowered kernel on real hardware;
* below this layer nothing defaults: every ``*_pallas`` kernel entry
  takes ``interpret`` as a required keyword.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple


class PallasUnsupportedError(RuntimeError):
    """A compiled (non-interpret) Pallas kernel was forced on a backend
    that cannot lower it.  Raised at dispatch time with the name of the
    flag that forced it, instead of surfacing an opaque Mosaic lowering
    failure from inside the kernel call."""


@functools.lru_cache(maxsize=1)
def tpu_available() -> bool:
    """True when the default JAX backend is a TPU (cached: the device
    set is fixed for the process lifetime).  A backend that fails to
    initialise raises here: a TPU host whose chip is unusable must not
    quietly run its kernels in interpret mode on the CPU."""
    return _platform() == "tpu"


def _platform() -> str:
    import jax

    return jax.devices()[0].platform


def resolve(use_pallas: Optional[bool] = None,
            interpret: Optional[bool] = None,
            flag: str = "use_pallas") -> Tuple[bool, bool]:
    """Resolve (use_pallas, interpret) with TPU autodetection for None.

    ``flag`` names the caller-facing switch in error messages (e.g. the
    compiler exposes the unit-fold selector as ``unit_fold_pallas``).
    Forcing the compiled kernel (``use_pallas=True, interpret=False``)
    on a non-TPU backend raises :class:`PallasUnsupportedError`; the
    autodetect default instead falls back to interpret mode off-TPU.
    """
    if use_pallas is None:
        use_pallas = tpu_available()
    if interpret is None:
        interpret = not tpu_available()
    if use_pallas and not interpret and not tpu_available():
        raise PallasUnsupportedError(
            f"{flag}=True requests the compiled Pallas kernel, but the "
            f"default JAX backend is '{_platform()}' (no Mosaic "
            f"lowering). Pass {flag}=None to autodetect the backend, or "
            f"interpret=True to run the kernel body in interpret mode.")
    return bool(use_pallas), bool(interpret)
