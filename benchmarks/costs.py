"""Bytes a query has to read, from the shapes alone: the yardstick of the
scan roofline. The same whatever implements the query."""

from __future__ import annotations

import re

#: bytes a landed value takes on the device (a string or a text is its
#: int32 dictionary code); validity is not counted: the tables hold no NULL
LANDED_WIDTH = {"long": 8, "double": 8, "date": 4, "int": 4, "string": 4,
                "text": 4}


def scan_bytes(tables: dict, text: str) -> int:
    """Rows x landed width of every column of `tables` the text names."""
    words = set(re.findall(r"[A-Za-z_][A-Za-z_0-9]*", text.lower()))
    total = 0
    for table in tables.values():
        for name, col in table["columns"].items():
            if name.lower() in words:
                total += table["num_rows"] * LANDED_WIDTH[col.type]
    return total


def rows_read(tables: dict, text: str) -> int:
    """Rows of every base table the text names."""
    words = set(re.findall(r"[A-Za-z_][A-Za-z_0-9]*", text.lower()))
    return sum(t["num_rows"] for name, t in tables.items()
               if name.lower() in words)
