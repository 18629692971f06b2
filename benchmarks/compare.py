"""The comparison that decides `correct`: a collected answer against the
plain reference's, column by column.

Integers, strings and dates (as days), the number of rows and their order
are exact: each cell that differs counts one mismatch, limit 0. A float
cell is read as its gap from the reference's, relative to the
reference's value; the widest gap of the answer is held to the cell's
limit (set from readings on the chip; PERF.md section 2).
"""

from __future__ import annotations

import math


def compare_answer(got: dict, want: dict):
    """Returns (exact mismatches, widest relative gap of a float cell)."""
    mismatches = 0
    widest = 0.0
    if list(got) != list(want):
        mismatches += len(set(got) ^ set(want)) or 1
    for name, ref_values in want.items():
        values = got.get(name)
        if values is None:
            continue
        if len(values) != len(ref_values):
            mismatches += abs(len(values) - len(ref_values))
        for a, b in zip(values, ref_values):
            if isinstance(b, float):
                if not isinstance(a, float) or math.isnan(a):
                    mismatches += 1
                else:
                    widest = max(widest, abs(a - b) / max(abs(b), 1e-300))
            elif a != b or type(a) is not type(b):
                mismatches += 1
    return mismatches, widest
