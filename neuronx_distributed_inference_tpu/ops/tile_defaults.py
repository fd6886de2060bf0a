"""Tile defaults sourced from the committed tuning table (KERN704).

Every Pallas kernel in ``ops/`` resolves its default tile sizes through
:func:`tile_default` instead of a hard-coded constant. The values live in
``analysis/tuning_table.json``, keyed by (kernel, shape-class, dtype), with
a ``provenance`` field: ``hand_picked`` entries mirror the historical
in-code constants (the kernel audit errors if they drift apart — see
KERN704 in ``analysis/kernel_audit.py``); a hardware session that re-runs
the ``scripts/decode_scaling.py`` sweeps promotes them to ``measured``, at
which point the table — not this file's fallbacks — is the source of truth.

This module must stay import-light (json + pathlib only): the kernels pull
defaults at trace time and must not drag the analysis package, jax-extras,
or anything traced into their import graph. A missing or unreadable table
falls back to the caller-supplied constant so ``ops/`` keeps working from a
bare checkout; the kernel-audit gate is what enforces the table exists and
agrees.
"""

import json
import pathlib
from contextlib import contextmanager
from functools import lru_cache
from typing import Dict, Iterator, Optional

#: the committed table, next to the suite that audits it
TUNING_TABLE_PATH = (
    pathlib.Path(__file__).resolve().parent.parent
    / "analysis"
    / "tuning_table.json"
)

#: accepted provenance values, in promotion order
PROVENANCES = ("hand_picked", "measured")


@lru_cache(maxsize=1)
def _load_table() -> Dict:
    try:
        with open(TUNING_TABLE_PATH) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    return data if isinstance(data, dict) else {}


def reload_table() -> None:
    """Drop the cached table (tests and ``--write-baseline`` use this)."""
    _load_table.cache_clear()


def table_entry(kernel: str, shape_class: str, dtype: str) -> Optional[Dict]:
    """The raw table entry ``{"tiles": {...}, "provenance": ...}`` or None."""
    entry = (
        _load_table()
        .get("kernels", {})
        .get(kernel, {})
        .get(shape_class, {})
        .get(str(dtype))
    )
    return entry if isinstance(entry, dict) else None


#: candidate-injection stack for the kernel audit's ``legal_tiles``
#: enumeration: overrides win over both the table and the fallback, so a
#: candidate exercises exactly the lookup path a committed table entry
#: would. Single-threaded by design (the analysis gate and tests).
_OVERRIDES: list = []


@contextmanager
def tile_overrides(kernel: str, tiles: Dict[str, int]) -> Iterator[None]:
    """Force ``tile_default(kernel, ...)`` to return ``tiles[param]`` for
    the duration of the context, regardless of table/fallback. NOTE: jitted
    kernel wrappers cache traces on shapes/statics only — callers must
    trace the unjitted function (see ``analysis.kernel_registry._unjit``)
    or clear jit caches around the context."""
    _OVERRIDES.append((kernel, dict(tiles)))
    try:
        yield
    finally:
        _OVERRIDES.pop()


def tile_default(
    kernel: str, shape_class: str, dtype: str, param: str, fallback: int
) -> int:
    """Default for one tile parameter of ``kernel`` at (shape_class, dtype).

    ``fallback`` is the historical hand-picked constant; it is used when the
    table has no entry (bare checkout, or a kernel/shape the table does not
    cover yet). While the entry's provenance is ``hand_picked`` the audit
    pins table == fallback, so the two can only diverge through a reviewed
    table regeneration.
    """
    for over_kernel, over_tiles in reversed(_OVERRIDES):
        if over_kernel == kernel and param in over_tiles:
            return int(over_tiles[param])
    entry = table_entry(kernel, shape_class, dtype)
    if entry is None:
        return fallback
    tiles = entry.get("tiles", {})
    value = tiles.get(param, fallback)
    return int(value) if isinstance(value, (int, float)) else fallback
