"""The work a query or a training iteration has to do, counted from the
query and the schema and not from the program: the numerators of the
roofline shares.

Every column a query reads counts all its rows at LANE_BYTES bytes: the
width of the int32 / float32 lanes (dictionary codes for strings) in
which the configurations' guarantees let the chip hold a value.  Counted
so, the bytes do not depend on which kernel or XLA fusion implements the
query, and stay defined when a later change swaps one for another.  The
count is a floor on what the chip reads while columns are shipped at
least that wide; a change that stores them narrower has to be weighed
against it in a benchmark change.
"""

from __future__ import annotations

from typing import Dict, Iterable

LANE_BYTES = 4


def scan_bytes(reads: Iterable[str], rows: Dict[str, int]) -> int:
    """Bytes a query must read: every row of each column it names
    (`table.column`), LANE_BYTES each."""
    return sum(rows[col.split(".", 1)[0]] * LANE_BYTES for col in set(reads))


def train_bytes(rows: int, columns: int) -> int:
    """Bytes one training iteration must read: its feature (and label)
    columns over the selected rows, LANE_BYTES each."""
    return rows * columns * LANE_BYTES
