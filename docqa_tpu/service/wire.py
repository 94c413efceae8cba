"""Boundary coercion for everything that crosses the service wire.

``json.dumps`` is the de-facto type system of the HTTP/broker/journal
plane, and it has two failure modes worth engineering around: values
that raise (numpy scalars on some versions, device arrays, arbitrary
objects) and values that serialize to NON-JSON (``float("nan")`` →
``NaN``, which strict parsers reject).  ``to_wire`` normalizes both:

* numpy scalars → native python via ``.item()``; numpy arrays →
  nested lists via ``.tolist()`` (then re-coerced, so an array of NaN
  still gets the non-finite treatment);
* non-finite floats → ``None``, with the dotted path of every such
  replacement recorded in a ``_nonfinite_fields`` list on the ROOT
  object when the root is a dict — the value is gone but the fact it
  was non-finite is preserved on the wire;
* dicts/lists/tuples recurse; keys coerce to ``str`` when they are
  numpy scalars.

Anything else (locks, Trace objects, device arrays) passes through
untouched so ``json.dumps`` still fails loudly — hiding those would
defeat the static ``wire-safety`` rule, whose job is to keep them from
reaching this function at all.
"""

from __future__ import annotations

import math
from typing import Any, List, Optional

try:  # numpy is an unconditional runtime dep, but stay import-safe
    import numpy as _np
except Exception:  # pragma: no cover - exercised only without numpy
    _np = None

NONFINITE_KEY = "_nonfinite_fields"


def _coerce(value: Any, path: str, flagged: List[str]) -> Any:
    if _np is not None:
        if isinstance(value, _np.generic):
            value = value.item()
        elif isinstance(value, _np.ndarray):
            value = value.tolist()
    if isinstance(value, float):
        if not math.isfinite(value):
            flagged.append(path)
            return None
        return value
    if isinstance(value, dict):
        out = {}
        for k, v in value.items():
            if _np is not None and isinstance(k, _np.generic):
                k = k.item()
            if not isinstance(k, str):
                k = str(k)
            out[k] = _coerce(v, f"{path}.{k}" if path else k, flagged)
        return out
    if isinstance(value, (list, tuple)):
        return [
            _coerce(v, f"{path}[{i}]", flagged)
            for i, v in enumerate(value)
        ]
    return value


def to_wire(payload: Any, flagged: Optional[List[str]] = None) -> Any:
    """Coerce ``payload`` for serialization (see module docstring).

    When any non-finite float was nulled and the coerced root is a
    dict, the root gains ``"_nonfinite_fields": [<dotted paths>]`` —
    every contract validator tolerates that key.  Pass ``flagged`` to
    collect the paths yourself (no root annotation happens then).
    """
    annotate = flagged is None
    paths: List[str] = [] if flagged is None else flagged
    out = _coerce(payload, "", paths)
    if annotate and paths and isinstance(out, dict):
        out[NONFINITE_KEY] = paths
    return out
