"""Scoped-VMEM sizing of the lane blocks the streaming Pallas kernels use.

On a TPU core a kernel gets 16 MiB of scoped VMEM (v5e default).  Every
grid step holds one block of each streamed input and output, and the
pipeline double-buffers each of them; a block's sublane rows pad to the
f32 tile height of 8 and its lane length must be a multiple of 128 unless
the block spans the whole dimension.  The body's own temporaries (the
matmul result, masks, casts) come on top, so the streamed blocks get half
the scoped limit and the other half is left to the body.

Every kernel call site that picks a lane block length goes through
``lane_block``, so the rule lives in one place.
"""

from __future__ import annotations

SCOPED_VMEM_BYTES = 16 << 20
STREAM_BUDGET_BYTES = SCOPED_VMEM_BYTES // 2
LANE = 128
SUBLANE = 8


def pad_rows(rows: int) -> int:
    """Rows a (rows, bl) f32 block occupies in VMEM (f32 tile height 8)."""
    return -(-rows // SUBLANE) * SUBLANE


def lane_block(
    length: int,
    *streamed_rows: int,
    scratch_rows: int = 0,
    fixed_bytes: int = 0,
    itemsize: int = 4,
) -> int:
    """Lane block length for streaming ``length`` columns through a kernel.

    ``streamed_rows`` has one entry per (rows, bl) input or output block,
    each counted double-buffered; ``scratch_rows`` counts single-buffered
    (rows, bl) scratch; ``fixed_bytes`` is what the whole-array operands
    (mixing blocks, weight tables) take.  Returns ``length`` itself when
    the whole row fits (a full-dimension block needs no alignment),
    otherwise the largest multiple of 128 that keeps every block under
    ``STREAM_BUDGET_BYTES``.
    """
    per_lane = itemsize * (
        2 * sum(pad_rows(r) for r in streamed_rows) + pad_rows(scratch_rows)
    )
    avail = STREAM_BUDGET_BYTES - fixed_bytes
    bl = (avail // per_lane) // LANE * LANE if avail > 0 else 0
    if length <= bl:
        return length
    if bl < LANE:
        raise ValueError(
            f"blocks of {streamed_rows} rows (+{scratch_rows} scratch, "
            f"{fixed_bytes} fixed bytes) do not fit one {LANE}-lane block "
            f"in {STREAM_BUDGET_BYTES} bytes of VMEM"
        )
    return bl
