"""TPC-H LINEITEM rows drawn with the distributions of the TPC-H
specification (v3.0.1, clause 4.2.3), numeric columns only.

Dates are whole days since 1992-01-01 (STARTDATE).  Flags are dictionary
codes: l_returnflag A=0, N=1, R=2; l_linestatus F=0, O=1.

  O_ORDERDATE   uniform in [STARTDATE, ENDDATE - 151 days]
  lines/order   uniform in [1, 7]
  L_QUANTITY    uniform in [1, 50]
  L_PARTKEY     uniform in [1, SF * 200,000]; P_RETAILPRICE from the key
  L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE
  L_DISCOUNT    uniform in [0.00, 0.10], step 0.01
  L_TAX         uniform in [0.00, 0.08], step 0.01
  L_SHIPDATE    O_ORDERDATE + uniform [1, 121]
  L_COMMITDATE  O_ORDERDATE + uniform [30, 90]
  L_RECEIPTDATE L_SHIPDATE + uniform [1, 30]
  L_RETURNFLAG  R or A (even odds) if L_RECEIPTDATE <= CURRENTDATE, else N
  L_LINESTATUS  O if L_SHIPDATE > CURRENTDATE, else F

The row count is fixed (6,001,215 at SF 1, as dbgen makes it): orders are
drawn until they cover it and the last order is cut, so every seed yields
the same number of rows.  Refresh batches (RF1) are more rows of the same
distribution.
"""
from __future__ import annotations

import datetime

import numpy as np

_DAY0 = datetime.date(1992, 1, 1)


def day(y: int, m: int, d: int) -> int:
    """Days since 1992-01-01."""
    return (datetime.date(y, m, d) - _DAY0).days


STARTDATE = 0
ENDDATE = day(1998, 12, 31)
CURRENTDATE = day(1995, 6, 17)
RETURNFLAG = {"A": 0.0, "N": 1.0, "R": 2.0}
LINESTATUS = {"F": 0.0, "O": 1.0}


def generate(rng: np.random.Generator, rows: int, params=None) -> dict:
    rows = int(rows)
    sf = float((params or {}).get("scale_factor", 1.0))
    n_orders = rows // 4 + 64 + 8 * int(np.sqrt(rows))
    lines = rng.integers(1, 8, n_orders)
    covered = int(np.searchsorted(np.cumsum(lines), rows)) + 1
    order = np.repeat(np.arange(covered), lines[:covered])[:rows]
    orderdate = rng.integers(STARTDATE, ENDDATE - 151 + 1, covered)[order]

    quantity = rng.integers(1, 51, rows)
    partkey = rng.integers(1, int(sf * 200_000) + 1, rows)
    retail = (90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1000)) / 100.0
    discount = rng.integers(0, 11, rows) / 100.0
    tax = rng.integers(0, 9, rows) / 100.0
    shipdate = orderdate + rng.integers(1, 122, rows)
    commitdate = orderdate + rng.integers(30, 91, rows)
    receiptdate = shipdate + rng.integers(1, 31, rows)
    returned = np.where(rng.random(rows) < 0.5, RETURNFLAG["R"],
                        RETURNFLAG["A"])
    returnflag = np.where(receiptdate <= CURRENTDATE, returned,
                          RETURNFLAG["N"])
    linestatus = np.where(shipdate > CURRENTDATE, LINESTATUS["O"],
                          LINESTATUS["F"])
    f32 = np.float32
    return {
        "l_quantity": quantity.astype(f32),
        "l_extendedprice": (quantity * retail).astype(f32),
        "l_discount": discount.astype(f32),
        "l_tax": tax.astype(f32),
        "l_returnflag": returnflag.astype(f32),
        "l_linestatus": linestatus.astype(f32),
        "l_shipdate": shipdate.astype(f32),
        "l_commitdate": commitdate.astype(f32),
        "l_receiptdate": receiptdate.astype(f32),
    }
