"""``repro`` — a production-scale bitmap-index system grown from the
paper's BIC core (see ROADMAP.md / ARCHITECTURE.md).

The documented entry point is the :mod:`repro.db` facade::

    import repro

    schema = repro.Schema([
        repro.Column.categorical("city", ["SF", "NY", "LA"]),
        repro.Column.binned("temp", edges=[-10, 0, 10, 20, 30, 45]),
    ])
    db = repro.BitmapDB(schema, path="/data/idx")   # durable session
    db.ingest(rows)
    res = db.query((repro.col("city") == "SF") &
                   repro.col("temp").between(15, 30))
    res.count, res.ids

    db2 = repro.open("/data/idx")                   # crash recovery

Lower layers stay directly importable (``repro.engine``, ``repro.store``,
``repro.core``, ...).  Symbols here resolve lazily — importing ``repro``
alone loads no jax-heavy module (the :mod:`repro.engine` idiom), so this
package ``__init__`` can never form an import cycle with them.
"""
from __future__ import annotations

import importlib

#: facade symbols re-exported at top level -> their home in repro.db
_DB_EXPORTS = ("BitmapDB", "Schema", "Column", "col", "Result", "open")

#: serving-port symbols -> their home in repro.serve.service
_SERVE_EXPORTS = ("BitmapService", "ServiceConfig")

_SUBMODULES = ("db", "engine", "store", "core", "data", "serve", "kernels",
               "checkpoint", "fault", "obs")

__all__ = sorted(_DB_EXPORTS + _SERVE_EXPORTS) + sorted(_SUBMODULES)


def __getattr__(name):
    if name in _SUBMODULES:
        return importlib.import_module(f"{__name__}.{name}")
    if name in _DB_EXPORTS:
        return getattr(importlib.import_module(f"{__name__}.db"), name)
    if name in _SERVE_EXPORTS:
        return getattr(
            importlib.import_module(f"{__name__}.serve.service"), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return __all__
