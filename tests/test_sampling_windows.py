"""The sampled window: the one way a wave avoids scanning every row.

A sampled wave filters and scores ``sample_rows_for(rows, pct, chunk)``
rows from ``sample_offset_for(i, nodes, rows)``, where one device rotates
over ``window_rows_of(high_water, rows_in_table, chunk, rows)`` (the rows
that ever held a node, rounded up to chunks) and ``None`` rows means the
window would cover the whole table, so the wave scans every row.  For the
cells' own geometries and the edges, two properties:

1. ``ceil(covered / rows)`` consecutive windows, from any start, cover
   every row that ever held a node;
2. every offset is chunk-aligned and every window lies inside the table
   and inside the rows it rotates over.
"""

from __future__ import annotations

import pytest

from k8s1m_tpu.control.coordinator import window_rows_of
from k8s1m_tpu.engine.cycle import sample_offset_for, sample_rows_for

# name: (rows that ever held a node, table rows, chunk, pct, window rows)
GEOMETRIES = {
    # kwok / spread / interpod / full-1m-pct5: the 53,248-row window
    "1m-pct5": (1 << 20, 1 << 20, 4096, 5, 53_248),
    # affinity-100k: 100K nodes in a table of 25 chunks
    "100k-in-102400": (100_000, 102_400, 4096, 5, 8192),
    # the same nodes in a power-of-two table: rotate over 25 chunks, not 32
    "100k-in-131072": (100_000, 131_072, 4096, 5, 8192),
    # fit-10k: pct 100 scans every row
    "10k-pct100": (10_000, 16_384, 4096, 100, None),
    # fewer nodes than one window: the one window still rotates in place
    "high-water-below-a-window": (10, 16_384, 4096, 50, 8192),
    # a high-water mark off a chunk edge rounds up to the next chunk
    "high-water-off-a-chunk-edge": (5_000, 65_536, 1024, 5, 4096),
    # a window that rounds up to the whole table is the full scan
    "table-is-one-window": (4096, 4096, 4096, 50, None),
    # pct 1 of a million rows: 10,486 rounded up to three chunks
    "1m-pct1": (1 << 20, 1 << 20, 4096, 1, 12_288),
}


def windows(used: int, table: int, chunk: int, pct: int):
    """(rows a window holds, rows it rotates over) as a one-device
    coordinator computes them (Coordinator._next_window)."""
    rows = sample_rows_for(table, pct, chunk)
    if rows is None:
        return table, table
    return rows, window_rows_of(used, table, chunk, rows)


def offset(i: int, rows: int, span: int) -> int:
    return 0 if rows == span else sample_offset_for(i, span, rows)


@pytest.mark.parametrize("name", GEOMETRIES)
def test_consecutive_windows_cover_every_row_that_held_a_node(name):
    used, table, chunk, pct, want = GEOMETRIES[name]
    rows, span = windows(used, table, chunk, pct)
    assert rows == (table if want is None else want)
    covered = -(-used // chunk) * chunk
    turns = -(-min(covered, span) // rows)
    for start in (0, 1, turns - 1, 1000):
        seen = [False] * span
        for i in range(start, start + max(turns, -(-span // rows))):
            o = offset(i, rows, span)
            seen[o:o + rows] = [True] * rows
        assert all(seen[:used]), (name, start)


@pytest.mark.parametrize("name", GEOMETRIES)
def test_every_window_is_chunk_aligned_and_inside_the_table(name):
    used, table, chunk, pct, _ = GEOMETRIES[name]
    rows, span = windows(used, table, chunk, pct)
    assert rows % chunk == 0 and span % chunk == 0
    assert rows <= span <= table
    turns = -(-span // rows)
    offsets = {offset(i, rows, span) for i in range(4 * turns)}
    assert len(offsets) == turns
    for o in offsets:
        assert o % chunk == 0
        assert 0 <= o and o + rows <= span
