"""Plain NumPy references for the benchmark's comparisons.

Imports nothing of the program under test: the answers here come from the
generated rows alone.  A filter query is a conjunction of predicates over
categorical columns, each ``(op, column, *args)``:

  ``("eq", c, v)``          c == v
  ``("in", c, (v, ...))``   c in the listed values
  ``("between", c, lo, hi)`` lo <= c <= hi (closed)
  ``("lt", c, v)``          c < v

A configuration's reference module draws such queries and generates the
rows; :class:`FilterReference` answers them.
"""
from __future__ import annotations

import numpy as np

#: seed streams: one generator per purpose, so adding a draw to one purpose
#: never shifts another's numbers
STREAMS = {"data": 1, "queries": 2, "arrivals": 3, "sample": 4, "burst": 5,
           "burst_queries": 6, "sessions": 7}


def rng(seed: int, stream: str) -> np.random.Generator:
    """The generator of ``stream`` for ``seed`` (any integer, negative or
    wider than 64 bits included)."""
    return np.random.default_rng([STREAMS[stream], seed % (1 << 64),
                                  (seed >> 64) % (1 << 64)])


def pred_values(domain: np.ndarray, pred: tuple, widen: int = 1
                ) -> np.ndarray:
    """The values of ``domain`` (sorted) that ``pred`` admits.  ``widen`` > 1
    turns a range into the superset of whole bins of ``widen`` consecutive
    values it touches: the answer a binned column would give."""
    op, _col = pred[0], pred[1]
    if op == "eq":
        keep = domain == pred[2]
    elif op == "in":
        keep = np.isin(domain, np.asarray(pred[2]))
    elif op == "between":
        keep = (domain >= pred[2]) & (domain <= pred[3])
    elif op == "lt":
        keep = domain < pred[2]
    else:
        raise ValueError(f"unknown predicate op {op!r}")
    if widen > 1 and op in ("between", "lt"):
        bins = np.arange(domain.size) // widen
        keep = np.isin(bins, bins[keep])
    return domain[keep]


def packbits(mask: np.ndarray) -> np.ndarray:
    """Boolean record mask -> packed uint32 row (record i is bit i % 32 of
    word i // 32), zero-padded to whole words."""
    n = mask.shape[-1]
    pad = (-n) % 32
    if pad:
        mask = np.concatenate(
            [mask, np.zeros(mask.shape[:-1] + (pad,), bool)], axis=-1)
    return np.packbits(mask, axis=-1, bitorder="little").view("<u4")


class FilterReference:
    """Exact answers of conjunctive filter queries over generated rows.

    ``domains``: column -> sorted array of its values; ``rows``: column ->
    the value of every row.  Counts come from one histogram per set of
    columns a template filters on, so every query of a window is checked;
    rows come from a direct mask evaluation."""

    def __init__(self, domains: dict, rows: dict):
        self.domains = {c: np.asarray(v) for c, v in domains.items()}
        self.n = len(next(iter(rows.values())))
        self._codes = {}
        for c, dom in self.domains.items():
            codes = np.searchsorted(dom, rows[c])
            if not np.array_equal(dom[codes], rows[c]):
                raise ValueError(f"column {c!r} holds a value outside its "
                                 "domain")
            self._codes[c] = codes.astype(np.int32)
        self._hists: dict = {}

    def _hist(self, cols: tuple) -> np.ndarray:
        h = self._hists.get(cols)
        if h is None:
            dims = tuple(self.domains[c].size for c in cols)
            flat = np.ravel_multi_index(tuple(self._codes[c] for c in cols),
                                        dims)
            h = np.bincount(flat, minlength=int(np.prod(dims))).reshape(dims)
            self._hists[cols] = h
        return h

    def _code_sets(self, query, widen: int) -> tuple[tuple, list]:
        cols, sets = [], []
        for pred in query:
            dom = self.domains[pred[1]]
            vals = pred_values(dom, pred, widen)
            cols.append(pred[1])
            sets.append(np.searchsorted(dom, vals))
        return tuple(cols), sets

    def count(self, query, widen: int = 1) -> int:
        cols, sets = self._code_sets(query, widen)
        if len(set(cols)) != len(cols):         # a column filtered twice
            return int(np.count_nonzero(self.mask(query, widen)))
        return int(self._hist(cols)[np.ix_(*sets)].sum())

    def mask(self, query, widen: int = 1) -> np.ndarray:
        m = np.ones(self.n, bool)
        for pred in query:
            dom = self.domains[pred[1]]
            lut = np.zeros(dom.size, bool)
            lut[np.searchsorted(dom, pred_values(dom, pred, widen))] = True
            m &= lut[self._codes[pred[1]]]
        return m

    def row(self, query, widen: int = 1) -> np.ndarray:
        return packbits(self.mask(query, widen))

    def key_rows(self, query) -> int:
        """Distinct key rows (column, value) the query references: what
        any index must read to answer it."""
        keys = set()
        for pred in query:
            dom = self.domains[pred[1]]
            keys.update((pred[1], v) for v in pred_values(dom, pred).tolist())
        return len(keys)


def index_rows(records: np.ndarray, num_keys: int) -> np.ndarray:
    """Key-major packed index of raw records (N, W): row k has bit i set
    when record i holds word k anywhere.  Words outside [0, num_keys)
    match nothing."""
    n, w = records.shape
    hit = np.zeros((num_keys, n), bool)
    idx = np.arange(n)
    for j in range(w):
        col = records[:, j]
        ok = (col >= 0) & (col < num_keys)
        hit[col[ok], idx[ok]] = True
    return packbits(hit)
