"""The least bytes a query has to move, counted from the configuration's
schema and the SQL text alone — blind to the program's plan, its kernels
and its planes, so no change to the program moves the numerator.

bytes = table rows x stored width of every schema column the statement
names + returned cells x the width of a result cell.
"""

from __future__ import annotations

import re

_IDENT = re.compile(r"[A-Za-z_][A-Za-z_0-9]*")


def statement(sql: str) -> str:
    """The query without its leading ``SET ...;`` options."""
    parts = [p.strip() for p in sql.split(";") if p.strip()]
    return [p for p in parts if not p.upper().startswith("SET ")][-1]


def columns_read(sql: str, config: dict) -> list:
    body = re.sub(r"'[^']*'", "''", statement(sql))  # drop string literals
    seen = set(_IDENT.findall(body))
    return [c for c in config["columns"] if c in seen]


def query_bytes(sql: str, config: dict, total_rows: int,
                result_rows: int, result_cols: int) -> int:
    width = sum(config["columns"][c]["stored_bytes"]
                for c in columns_read(sql, config))
    return (total_rows * width
            + result_rows * result_cols * config["result_cell_bytes"])


def least_seconds(n_bytes: float, peak: dict) -> float:
    return n_bytes / peak["hbm_bytes_per_s"]
