"""In-kernel row gather from an HBM table by scalar-prefetched ids.

A TPU DMA cannot cut a single row out of an HBM array: the table is stored
in ``(sublanes, 128)`` tiles (8 rows for 32-bit dtypes, 16 for 16-bit), and
a copy has to start and end on a tile boundary. So each requested row is
fetched as the aligned group of rows that holds it, into a VMEM staging
tile, and the row is then copied out of the staging tile at its dynamic
offset. The copies of one staging round are all issued before the first
wait, so they overlap. The table's row count must be a multiple of the
group size (:func:`pad_rows`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["group_rows", "pad_rows", "gather_rows", "stage_shape"]


def group_rows(dtype) -> int:
    """Rows per HBM tile for ``dtype``: 8 for 32-bit, 16 for 16-bit."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def pad_rows(table: jax.Array) -> jax.Array:
    """Pad a ``(n, d)`` table with zero rows to a whole number of tiles
    (a no-op, and no copy, when ``n`` already is one)."""
    g = group_rows(table.dtype)
    extra = -table.shape[0] % g
    return jnp.pad(table, ((0, extra), (0, 0))) if extra else table


def gather_rows(table_hbm, row_id, rows, stage, sem) -> None:
    """Fill the VMEM tile ``rows`` with table rows, row ``r`` being
    ``table[row_id(r)]``.

    ``row_id`` maps a traced row number to an int32 scalar (it reads
    scalar-prefetched ids). ``stage`` is a ``(sub, group, d)`` VMEM tile of
    the table's dtype, with ``sub`` dividing the row count: rows land
    ``sub`` at a time, which bounds the staging VMEM whatever the count.
    ``sem`` is one DMA semaphore.
    """
    n = rows.shape[0]
    sub, g = stage.shape[:2]
    assert n % sub == 0, (n, sub)

    def one_sub(s0, c):
        base = s0 * sub

        def copy(r):
            start = pl.multiple_of((row_id(base + r) // g) * g, g)
            return pltpu.make_async_copy(
                table_hbm.at[pl.ds(start, g)], stage.at[r], sem
            )

        def issue(r, c):
            copy(r).start()
            return c

        def land(r, c):
            copy(r).wait()
            return c

        def pick(r, c):
            off = row_id(base + r) % g
            rows[pl.ds(base + r, 1), :] = stage[r, pl.ds(off, 1), :].astype(
                rows.dtype
            )
            return c

        jax.lax.fori_loop(0, sub, issue, 0)
        jax.lax.fori_loop(0, sub, land, 0)
        return jax.lax.fori_loop(0, sub, pick, c)

    jax.lax.fori_loop(0, n // sub, one_sub, 0)


def stage_shape(n: int, d: int, dtype, sub: int = 16) -> tuple[int, int, int]:
    """Staging tile for :func:`gather_rows` of ``n`` rows: the largest row
    count up to ``sub`` that divides ``n`` lands at a time."""
    sub = max(s for s in range(1, min(sub, n) + 1) if n % s == 0)
    return (sub, group_rows(dtype), d)
