"""Model families: what the benchmark knows of a configuration's shapes.

A configuration's ``model`` block names its family (``"family":
"dense"``), and :func:`load` finds ``families/<family>.py`` by that
name, so a later change adds a family by adding a file.  A family
module gives:

- ``segments(m)``: ``[(n_layers, leaves)]`` in the program's order of
  ``params["segments"]``, ``leaves`` being one layer's ``(path, shape,
  fan_in)`` (fan_in 0 = norm gain);
- ``outer_leaves(m)``: ``[(name, shape, fan_in)]`` of the leaves outside
  the segments, in the order of their keys;
- ``span_flops(m, start, n)``, ``head_flops(m, rows)``: the model FLOPs
  of ``n`` consecutive tokens from ``start``, and of ``rows`` logits rows;
- ``paged_decode_cost(m, lengths, page)``: (FLOPs, bytes) of the paged
  decode kernel's calls of one decode step.

The plain reference of a family's equations is the module that the
configuration's ``reference`` names.
"""

from __future__ import annotations

import importlib
from pathlib import Path
from typing import Dict


def load(m: Dict):
    """The family module that the ``model`` block ``m`` names."""
    if "family" not in m:
        raise LookupError("the configuration's model block names no family")
    name = m["family"]
    if not (Path(__file__).parent / f"{name}.py").is_file():
        raise LookupError(f"no model family {name!r}: "
                          f"benchmarks/chip/families/{name}.py is missing")
    return importlib.import_module(f"{__name__}.{name}")
