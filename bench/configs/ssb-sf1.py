"""Star Schema Benchmark at SF 1 as a bitmap join index: the plain
reference beside ``ssb-sf1.json``.

Generates LINEORDER and its dimension tables from the seed at the sizes
of the JSON file, joins the 14 attributes the 13 SSB queries filter on
onto each LINEORDER row, and draws the 13 query templates with their
substitution parameters at the spec's domain positions.  Imports nothing
of the program: NumPy and the benchmark's own reference helpers only.
"""
from __future__ import annotations

import numpy as np

from bench import reference

#: TPC-H nation -> region (AFRICA 0, AMERICA 1, ASIA 2, EUROPE 3,
#: MIDDLE EAST 4), nations 0..24 in TPC-H order
NATION_REGION = np.array([0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0,
                          1, 2, 3, 4, 2, 3, 3, 1], np.int32)

TEMPLATES = ("Q1.1", "Q1.2", "Q1.3", "Q2.1", "Q2.2", "Q2.3", "Q3.1",
             "Q3.2", "Q3.3", "Q3.4", "Q4.1", "Q4.2", "Q4.3")


def _dates(cfg):
    first = np.datetime64(cfg["date_first"])
    days = np.arange(first, np.datetime64(cfg["date_last"]) + 1)
    year = days.astype("datetime64[Y]").astype(np.int32) + 1970
    month = days.astype("datetime64[M]").astype(np.int32) % 12 + 1
    doy = (days - days.astype("datetime64[Y]")).astype(np.int32) + 1
    return {"d_year": year,
            "d_yearmonthnum": year * 100 + month,
            "d_weeknuminyear": (doy - 1) // 7 + 1}


def columns(cfg) -> list:
    """(name, sorted values) of every indexed column, in key-row order."""
    dates = _dates(cfg)
    q0, q1 = cfg["quantity_range"]
    d0, d1 = cfg["discount_range"]
    nations = np.arange(25)
    cities = (nations[:, None] * 10 + np.arange(10)).ravel()
    cats = (np.arange(1, 6)[:, None] * 10 + np.arange(1, 6)).ravel()
    brands = (cats[:, None] * 100 + np.arange(1, 41)).ravel()
    domains = {
        "d_year": np.unique(dates["d_year"]),
        "d_yearmonthnum": np.unique(dates["d_yearmonthnum"]),
        "d_weeknuminyear": np.unique(dates["d_weeknuminyear"]),
        "lo_discount": np.arange(d0, d1 + 1),
        "lo_quantity": np.arange(q0, q1 + 1),
        "p_mfgr": np.arange(1, 6),
        "p_category": cats,
        "p_brand1": brands,
        "s_region": np.arange(5), "s_nation": nations, "s_city": cities,
        "c_region": np.arange(5), "c_nation": nations, "c_city": cities,
    }
    out = [(name, domains[name].astype(np.int32).tolist())
           for name in cfg["columns"]]
    for name, vals in out:
        if len(vals) != cfg["columns"][name]:
            raise ValueError(f"{name}: {len(vals)} values, the config "
                             f"states {cfg['columns'][name]}")
    return out


def generate(cfg, seed: int) -> dict:
    """LINEORDER rows with the joined dimension attributes: column ->
    int32 array of ``lineorder_rows`` values."""
    r = reference.rng(seed, "data")
    i32 = np.int32

    def geo(n):
        nation = r.integers(0, 25, n, dtype=i32)
        return (NATION_REGION[nation], nation,
                nation * 10 + r.integers(0, 10, n, dtype=i32))

    c_region, c_nation, c_city = geo(cfg["customer_rows"])
    s_region, s_nation, s_city = geo(cfg["supplier_rows"])
    npart = cfg["part_rows"]
    p_mfgr = r.integers(1, 6, npart, dtype=i32)
    p_category = p_mfgr * 10 + r.integers(1, 6, npart, dtype=i32)
    p_brand1 = p_category * 100 + r.integers(1, 41, npart, dtype=i32)
    dates = _dates(cfg)
    order_days = int((np.datetime64(cfg["orderdate_last"])
                      - np.datetime64(cfg["date_first"])).astype(int)) + 1

    n = cfg["lineorder_rows"]
    od = r.integers(0, order_days, n, dtype=i32)
    ck = r.integers(0, cfg["customer_rows"], n, dtype=i32)
    pk = r.integers(0, npart, n, dtype=i32)
    sk = r.integers(0, cfg["supplier_rows"], n, dtype=i32)
    q0, q1 = cfg["quantity_range"]
    d0, d1 = cfg["discount_range"]
    rows = {
        "d_year": dates["d_year"][od],
        "d_yearmonthnum": dates["d_yearmonthnum"][od],
        "d_weeknuminyear": dates["d_weeknuminyear"][od],
        "lo_discount": r.integers(d0, d1 + 1, n, dtype=i32),
        "lo_quantity": r.integers(q0, q1 + 1, n, dtype=i32),
        "p_mfgr": p_mfgr[pk], "p_category": p_category[pk],
        "p_brand1": p_brand1[pk],
        "s_region": s_region[sk], "s_nation": s_nation[sk],
        "s_city": s_city[sk],
        "c_region": c_region[ck], "c_nation": c_nation[ck],
        "c_city": c_city[ck],
    }
    return {name: rows[name].astype(i32) for name in cfg["columns"]}


def draw(cfg, r: np.random.Generator, template: str) -> tuple:
    """One query of ``template`` with its substitution parameters drawn
    at the spec's positions: a tuple of predicates (see
    :mod:`bench.reference`)."""
    def year(lo=1992, hi=1998):
        return int(r.integers(lo, hi + 1))

    def disc():                      # a 3-wide discount window, e.g. 1..3
        d = int(r.integers(0, 9))
        return ("between", "lo_discount", d, d + 2)

    def qty10():                     # a 10-wide quantity window, e.g. 26..35
        q = int(r.integers(1, 42))
        return ("between", "lo_quantity", q, q + 9)

    def category():
        return int(r.integers(1, 6)) * 10 + int(r.integers(1, 6))

    def region():
        return int(r.integers(0, 5))

    def two_cities():                # two cities of one nation
        n = int(r.integers(0, 25))
        a, b = r.choice(10, 2, replace=False)
        return (n * 10 + int(a), n * 10 + int(b))

    def yearmonth():
        return year() * 100 + int(r.integers(1, 13))

    t = template
    if t == "Q1.1":
        return (("eq", "d_year", year()), disc(), ("lt", "lo_quantity", 25))
    if t == "Q1.2":
        return (("eq", "d_yearmonthnum", yearmonth()), disc(), qty10())
    if t == "Q1.3":
        return (("eq", "d_weeknuminyear", int(r.integers(1, 54))),
                ("eq", "d_year", year()), disc(), qty10())
    if t == "Q2.1":
        return (("eq", "p_category", category()),
                ("eq", "s_region", region()))
    if t == "Q2.2":
        b = category() * 100 + int(r.integers(1, 34))
        return (("between", "p_brand1", b, b + 7),
                ("eq", "s_region", region()))
    if t == "Q2.3":
        return (("eq", "p_brand1", category() * 100 + int(r.integers(1, 41))),
                ("eq", "s_region", region()))
    if t == "Q3.1":
        reg, y = region(), year(1992, 1993)
        return (("eq", "c_region", reg), ("eq", "s_region", reg),
                ("between", "d_year", y, y + 5))
    if t == "Q3.2":
        n, y = int(r.integers(0, 25)), year(1992, 1993)
        return (("eq", "c_nation", n), ("eq", "s_nation", n),
                ("between", "d_year", y, y + 5))
    if t == "Q3.3":
        cities, y = two_cities(), year(1992, 1993)
        return (("in", "c_city", cities), ("in", "s_city", cities),
                ("between", "d_year", y, y + 5))
    if t == "Q3.4":
        cities = two_cities()
        return (("in", "c_city", cities), ("in", "s_city", cities),
                ("eq", "d_yearmonthnum", yearmonth()))
    if t in ("Q4.1", "Q4.2"):
        reg, m = region(), int(r.integers(1, 5))
        q = (("eq", "c_region", reg), ("eq", "s_region", reg),
             ("in", "p_mfgr", (m, m + 1)))
        if t == "Q4.2":
            y = year(1992, 1997)
            q += (("in", "d_year", (y, y + 1)),)
        return q
    if t == "Q4.3":
        reg, y = region(), year(1992, 1997)
        nation = int(r.choice(np.flatnonzero(NATION_REGION == reg)))
        return (("eq", "s_nation", nation), ("eq", "c_region", reg),
                ("in", "d_year", (y, y + 1)),
                ("eq", "p_category", category()))
    raise ValueError(f"unknown SSB template {template!r}")


#: the control's broken guarantee: ranges answered as the superset of
#: whole bins of this many consecutive values (a binned column's answer)
CONTROL_BIN = 4
