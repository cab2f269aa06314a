"""Shared helpers for the Pallas kernel layer.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are
validated on CPU with ``interpret=True``.  ``resolve_interpret`` picks
interpret mode automatically when no explicit choice is given.

``tuned_knobs`` implements the dispatchers' knob resolution order:
an explicit caller value wins; a ``None`` knob consults the
``repro.tune`` config cache for a winner tuned at this (op, shape,
dtype, backend) key; on a cache miss the caller-supplied analytic
fallback (typically derived from ``plan_rif``) applies.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

import jax

__all__ = ["cdiv", "round_up", "env_flag", "resolve_interpret",
           "tuned_knobs", "ring_rif", "note_knobs", "KNOBS_USED",
           "MXU_LANE", "VMEM_BYTES"]

# TPU v5e hardware shape constants (see benchmarks/hw.py for the full set)
MXU_LANE = 128          # lane dimension granularity
SUBLANE = 8             # float32 sublane granularity
VMEM_BYTES = 128 * 2**20  # ~128 MiB VMEM per core (v5e: 128MB unified)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def env_flag(name: str) -> Optional[bool]:
    """Parse a boolean environment variable: unset -> None; empty, "0",
    "false", "no", "off" (any case) -> False; anything else -> True."""
    raw = os.environ.get(name)
    if raw is None:
        return None
    return raw.strip().lower() not in ("", "0", "false", "no", "off")


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Explicit flag wins; else $REPRO_FORCE_INTERPRET (truthy values
    only — "0"/"false"/empty read as unset); else interpret everywhere
    except real TPU."""
    if interpret is not None:
        return interpret
    if env_flag("REPRO_FORCE_INTERPRET"):
        return True
    return jax.default_backend() != "tpu"


def ring_rif(rif: Optional[int], block_bytes: int) -> int:
    """Resolve a still-``None`` ring depth to the ``plan_rif`` analytic
    default for ``block_bytes`` requests — the last tier of the
    explicit → tune-cache → analytic dispatch order, shared by every
    ring-emitted kernel's dispatcher."""
    if rif is not None:
        return rif
    # deferred: repro.core.__init__ -> decouple -> kernels ops would
    # cycle on a top-level repro.core.pipeline import
    from repro.core.pipeline import plan_rif
    return plan_rif(block_bytes).rif


# (op, dims) -> the knobs a dispatcher last resolved for that call; read
# by benchmarks to report what actually ran
KNOBS_USED: Dict[Tuple[str, Tuple[int, ...]], Dict[str, Any]] = {}


def note_knobs(op: str, dims, **knobs) -> None:
    """Record the fully resolved knobs of one dispatch of ``op``."""
    KNOBS_USED[(op, tuple(int(d) for d in dims))] = knobs


def tuned_knobs(op: str, dims, dtype, interpret: bool, **defaults):
    """Resolve a dispatcher's ``None`` knobs: tune-cache winner first,
    caller-supplied analytic default second.

    ``defaults`` maps knob name -> (caller value, fallback); a caller
    value of ``None`` means "not specified".  Returns the filled dict.
    """
    from repro.tune import dispatch_config  # deferred: kernels <-> tune
    cfg = dispatch_config(op, dims, dtype, interpret)
    return {k: (v if v is not None else cfg.get(k, fb))
            for k, (v, fb) in defaults.items()}
