"""Device programs: the attention kernels of ``models`` and the library's
own (``device_digest``, ``device_pack``).

The attention exports resolve on first use (PEP 562): the save path
imports ``ops.device_pack`` and ``ops.device_digest`` inside an
application's first take, and must not pay there for the Pallas
kernels' imports, which it never runs.
"""

import importlib

_EXPORTS = {
    "causal_attention": ".attention",
    "flash_causal_attention": ".flash_attention",
    "ring_causal_attention": ".ring_attention",
}

__all__ = list(_EXPORTS)


def __getattr__(name: str):
    if name in _EXPORTS:
        value = getattr(importlib.import_module(_EXPORTS[name], __name__), name)
        globals()[name] = value
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
