"""Measured kernel-path defaults, keyed by device kind.

The reference selects conv algorithms by measuring each candidate on the
real device and caching the winner (its cudnnFindConvolutionForwardAlgorithm
sweep, src/ops/conv_2d.cu:864-922).  The TPU analogue: alternative
XLA lowerings (custom max-pool VJP, phase-decomposed strided dgrad,
channels-minor concat) are benchmarked on chip
(``scripts/kernel_microbench.py``, one chip command — ROADMAP S4) and
the winners committed in ``tuned_defaults.json`` next to this module.
Resolution order for each
flag: explicit env var  >  tuned file entry for this device kind  >
built-in default.  The file is committed, so the tuning survives into
every later run on the same device kind; on device kinds never measured
(e.g. the CPU test mesh) the built-in default applies unchanged.
"""

from __future__ import annotations

import functools
import json
import os

_TUNED_PATH = os.path.join(os.path.dirname(__file__), "tuned_defaults.json")


@functools.lru_cache(maxsize=1)
def _tuned_table() -> dict:
    try:
        with open(_TUNED_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


@functools.lru_cache(maxsize=1)
def _device_kind() -> str:
    # imported lazily: the table is consulted at trace time, when the
    # backend is already up (never on the import path)
    import jax

    return jax.devices()[0].device_kind


def flag_enabled(env_var: str, tuned_key: str, default: bool = True) -> bool:
    """``env_var`` ("0"/"1") wins; else the tuned table entry for this
    device kind; else ``default``.  Table lookups only happen when the
    committed table is non-empty, so untuned installs never pay the
    backend query."""
    env = os.environ.get(env_var)
    if env is not None:
        return env != "0"
    table = _tuned_table()
    if table:
        by_kind = table.get(tuned_key, {})
        if _device_kind() in by_kind:
            return bool(by_kind[_device_kind()])
    return default
