"""The comparison that decides ``correct``: a served table against the
reference's full answer (``bench/reference.py``), under the query's own
semantics.

A grouped answer (``"result": {"keys": [...], "value": ..., "order":
"desc"|"asc", "limit": k}`` in the traffic file) is right when the table
has ``min(k, groups)`` rows, every row names a distinct group with that
group's exact value, the values come in the query's order, and they are
the ``k`` best values of the whole answer.  Rows that tie on the value may
come in any order, and which of several tied groups fill the last places
is free: ORDER BY fixes no more.  A bare count (``"result": {"value":
...}``) is right when the table is one row holding the exact count.  Plain
rows (``"result": {"columns": [...]}``) are right when the table holds
exactly the answer's rows, in any order, each column's exact values.
"""
from __future__ import annotations

import numpy as np


def check_table(cols: dict, nrows: int, answer: dict, result: dict):
    """``None`` when the served table is a right answer, else the first
    reason it is not."""
    if "columns" in result:
        return _check_rows(cols, nrows, answer, result["columns"])
    value = result["value"]
    if value not in cols:
        return f"no column {value!r} (columns {sorted(cols)})"
    vals = np.asarray(cols[value]).astype(np.int64).ravel()
    if vals.shape[0] != nrows:
        return f"column {value!r} has {vals.shape[0]} rows, table {nrows}"
    keys = result.get("keys") or []
    if not keys:
        want = answer.get((), 0)
        if nrows != 1 or int(vals[0]) != want:
            return f"count {vals.tolist()} != {want}"
        return None
    missing = [k for k in keys if k not in cols]
    if missing:
        return f"no key columns {missing} (columns {sorted(cols)})"
    key_cols = [np.asarray(cols[k]).astype(np.int64).ravel() for k in keys]
    desc = result.get("order", "desc") == "desc"
    best = sorted(answer.values(), reverse=desc)[:result["limit"]]
    if nrows != len(best):
        return f"{nrows} rows, the answer has {len(best)}"
    seen = set()
    for i in range(nrows):
        key = tuple(int(c[i]) for c in key_cols)
        if key in seen:
            return f"group {key} twice"
        seen.add(key)
        if answer.get(key) != int(vals[i]):
            return f"group {key}: {value}={int(vals[i])}, the answer " \
                   f"has {answer.get(key)}"
    if vals.tolist() != best:
        return f"{value} in served order {vals.tolist()} != best {best}"
    return None


def _check_rows(cols: dict, nrows: int, answer: list, columns: list):
    missing = [c for c in columns if c not in cols]
    if missing:
        return f"no columns {missing} (columns {sorted(cols)})"
    vals = [np.asarray(cols[c]).astype(np.int64).ravel() for c in columns]
    if any(v.shape[0] != nrows for v in vals):
        return f"columns of {[v.shape[0] for v in vals]} rows, table {nrows}"
    got = sorted(tuple(int(v[i]) for v in vals) for i in range(nrows))
    want = sorted(tuple(int(x) for x in row) for row in answer)
    if got != want:
        return f"rows {got[:4]} != the answer's {want[:4]}"
    return None
