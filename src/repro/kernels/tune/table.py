"""Persistent per-bucket tuning table: schema, loader, fallback rules.

The autotuner (:mod:`repro.kernels.tune.search`) measures each variant's
candidate schedules per power-of-two width bucket and commits the winners
to a JSON table under ``src/repro/kernels/tune/tables/<backend>.json``.
At runtime the ops wrappers resolve their schedule parameters through
:func:`lookup`; anything that goes wrong — missing file, corrupt JSON,
schema drift, a table generated for another backend, an unknown bucket,
or parameter values outside the declared search space — silently falls
back to the module defaults the kernels shipped with.  A bad table can
therefore only ever cost performance, never correctness or an import
error (the loader never raises).

Table schema (``schema_version`` 1)::

    {
      "schema_version": 1,
      "backend": "cpu-interpret",
      "generated_by": "benchmarks/autotune.py --retune",
      "entries": {
        "circle_score_argmin/1024": {"block_l": 128, "shift_chunk": 16},
        ...
      }
    }

Entry keys are ``"<variant>/<bucket>"``; values carry exactly the
variant's search-space parameters.  The backend key is coarse on purpose
(``cpu-interpret`` / ``tpu-mosaic`` / ...): interpret-mode timings are
dominated by grid-step count, not host microarchitecture, so one
committed CPU table transfers across CI runners, while a Mosaic table
must never be consumed by an interpret run (hence the mismatch → defaults
rule).
"""

from __future__ import annotations

import json
import logging
import os
import warnings
from dataclasses import dataclass, field
from pathlib import Path
from typing import Mapping

import jax

from repro.kernels.circle_score.kernel import DEFAULT_BLOCK_L, SHIFT_CHUNK

from .space import SPACES

__all__ = [
    "SCHEMA_VERSION",
    "DEFAULTS",
    "TuningTable",
    "bucket_for",
    "current_backend",
    "default_table_path",
    "get_table",
    "load_table",
    "lookup",
    "reset_cache",
]

SCHEMA_VERSION = 1

# Environment override consumed by get_table(): point it at an alternate
# table file (tests, nightly drift checks) without touching the tree.
TABLE_ENV = "REPRO_TUNE_TABLE"

# The untuned schedules — what every kernel shipped with before the
# autotuner existed and what every fallback resolves to.  The circle
# family's values come straight from the kernel module so the two can
# never drift; flash/ssd defaults mirror their kernels' historical
# signature defaults (asserted against the search spaces below).
DEFAULTS: Mapping[str, Mapping[str, int]] = {
    "circle_score": {"block_l": DEFAULT_BLOCK_L},
    "circle_score_argmin": {
        "block_l": DEFAULT_BLOCK_L, "shift_chunk": SHIFT_CHUNK,
    },
    "circle_score_segmin": {
        "block_l": DEFAULT_BLOCK_L, "shift_chunk": SHIFT_CHUNK,
    },
    "flash_attention": {"block_q": 128, "block_k": 128},
    "ssd_scan": {"chunk": 256},
}

for _v, _params in DEFAULTS.items():
    assert set(_params) == set(SPACES[_v]), (_v, _params)
    assert all(_params[_k] in SPACES[_v][_k] for _k in _params), (_v, _params)


def current_backend() -> str:
    """Coarse backend key for table files: execution target + lowering."""
    b = jax.default_backend()
    return f"{b}-mosaic" if b == "tpu" else f"{b}-interpret"


def tables_dir() -> Path:
    return Path(__file__).resolve().parent / "tables"


def default_table_path(backend: str | None = None) -> Path:
    return tables_dir() / f"{backend or current_backend()}.json"


def bucket_for(width: int) -> int:
    """The power-of-two lane bucket a launch of ``width`` lands in."""
    from repro.kernels.circle_score.ops import bucket_width

    return bucket_width(width)


@dataclass(frozen=True)
class TuningTable:
    """Validated, immutable view of one table file (or the defaults)."""

    backend: str
    entries: Mapping[str, Mapping[str, int]] = field(default_factory=dict)
    source: str = "<defaults>"

    def lookup(self, variant: str, width: int) -> dict[str, int]:
        """Schedule parameters for a ``width``-wide launch of ``variant``.

        Unknown buckets (and every fallback path that produced an empty
        table) resolve to :data:`DEFAULTS`; unknown variants are a
        programming error and raise.
        """
        defaults = DEFAULTS[variant]
        entry = self.entries.get(f"{variant}/{bucket_for(width)}")
        if entry is None:
            return dict(defaults)
        return {**defaults, **entry}


def _valid_entry(key: str, params: object) -> bool:
    """One table entry is usable iff its key parses to a known
    (variant, bucket) and every parameter sits inside the declared search
    space — anything else is skipped (that bucket then uses defaults)."""
    variant, _, bucket = key.partition("/")
    if variant not in SPACES or not bucket.isdigit():
        return False
    if not isinstance(params, dict) or set(params) - set(SPACES[variant]):
        return False
    return all(
        isinstance(v, int) and not isinstance(v, bool)
        and v in SPACES[variant][k]
        for k, v in params.items()
    )


def load_table(
    path: str | os.PathLike | None = None, backend: str | None = None
) -> TuningTable:
    """Load and validate a tuning table; never raises.

    Fallback ladder (each rung warns once and lands on defaults):
    missing file → defaults; unparseable JSON / non-object top level →
    defaults; ``schema_version`` mismatch → defaults; ``backend``
    mismatch → defaults; individually invalid entries are dropped while
    the rest of the table still applies.
    """
    backend = backend or current_backend()
    p = Path(path) if path is not None else default_table_path(backend)
    if not p.is_file():
        return TuningTable(backend=backend)
    try:
        raw = json.loads(p.read_text())
    except (OSError, ValueError) as e:
        warnings.warn(
            f"tuning table {p} unreadable ({e}); using kernel defaults",
            RuntimeWarning, stacklevel=2,
        )
        return TuningTable(backend=backend)
    if not isinstance(raw, dict) or raw.get("schema_version") != SCHEMA_VERSION:
        warnings.warn(
            f"tuning table {p} has unsupported schema "
            f"{raw.get('schema_version') if isinstance(raw, dict) else raw!r}"
            f" (want {SCHEMA_VERSION}); using kernel defaults",
            RuntimeWarning, stacklevel=2,
        )
        return TuningTable(backend=backend)
    if raw.get("backend") != backend:
        warnings.warn(
            f"tuning table {p} was tuned for backend {raw.get('backend')!r} "
            f"but this process runs {backend!r}; using kernel defaults",
            RuntimeWarning, stacklevel=2,
        )
        return TuningTable(backend=backend)
    entries = raw.get("entries")
    if not isinstance(entries, dict):
        entries = {}
    kept = {
        k: dict(v) for k, v in entries.items() if _valid_entry(k, v)
    }
    dropped = set(entries) - set(kept)
    if dropped:
        warnings.warn(
            f"tuning table {p}: dropped invalid entries {sorted(dropped)}",
            RuntimeWarning, stacklevel=2,
        )
    return TuningTable(backend=backend, entries=kept, source=str(p))


_CACHE: TuningTable | None = None


def get_table() -> TuningTable:
    """The process-wide table: loaded once from ``$REPRO_TUNE_TABLE`` or
    the committed per-backend file, then cached (the hot path is one dict
    probe per launch)."""
    global _CACHE
    if _CACHE is None:
        _CACHE = load_table(os.environ.get(TABLE_ENV) or None)
        if not _CACHE.entries:
            # no table for this backend (the chip has none committed):
            # every launch takes DEFAULTS — said once per process
            logging.getLogger(__name__).warning(
                "no tuning entries for backend %r; the kernels' default "
                "schedules apply", _CACHE.backend,
            )
    return _CACHE


def reset_cache() -> None:
    """Forget the cached table (tests / after a retune wrote a new file)."""
    global _CACHE
    _CACHE = None


def lookup(variant: str, width: int) -> dict[str, int]:
    """Module-level convenience: :func:`get_table` + table lookup."""
    return get_table().lookup(variant, width)
