"""Blocked slot gather: the packed program's postings copies as one Pallas
TPU kernel with a whole block of copies in flight.

`jax.vmap(dynamic_slice)` over a slot table lowers on the TPU to one `while`
loop a posting stream with one iteration a slot, each a 2 KB copy in and a
2 KB copy out that waits for the one before: 1.96 us an iteration, 386 ms
of a 517 ms program at 256 bodies x 256 slots (PERF.md §5, PR 25). The bytes
are a millisecond's worth; the cost is that the copies are issued one at a
time. Here the grid walks blocks of BLOCK slots; a step starts the
BLOCK x streams DMAs out of HBM, then waits for them, then writes its block
of each output: 21 ms at the same shape (PERF.md §6, PR 28).

A slot starts anywhere (`term_start + i * chunk`), and a DMA moves whole
128-lane rows. So each stream is viewed as rows of 128 (a bitcast of the
1-D array: the 1,024-word tile and the (8, 128) tile are the same bytes), a
slot's DMA brings the chunk/128 + 1 rows its postings lie in, and the lanes
are put right in VMEM: a roll by the start's offset in its row, and a select
between each row and the next.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128     # = 1 << 7
# slots a grid step; divides every Q_pad * S of the packed lane's buckets
# (the least is 1 x 32), so the grid follows Q_pad * S and nothing else
BLOCK = 32


def gather_slots(starts: jax.Array, streams: tuple, *, chunk: int,
                 interpret: bool = False) -> tuple:
    """out[j][n, :] = streams[j][starts[n] : starts[n] + chunk], bit for bit.

    starts i32[N], N a multiple of BLOCK, every start in [0, P - chunk].
    streams: 1-D arrays of one length P (a multiple of 128, at least
    chunk + 128) and 32-bit dtypes. chunk: a multiple of 128.
    `interpret` runs the kernel off the TPU (the tier-1 tests).
    """
    N, = starts.shape
    P, = streams[0].shape
    rows = chunk // LANES           # rows of a slot's output
    n_copy = rows + 1               # rows its postings can lie in
    R = P // LANES
    assert chunk % LANES == 0 and P % LANES == 0 and R >= n_copy, (chunk, P)
    assert N % BLOCK == 0, N
    n_streams = len(streams)

    # Every index below is 32-bit on purpose. The package turns 64-bit types
    # on, and Mosaic lowers no 64-bit index (the interpreter takes them all):
    # hence the typed loop bounds, `jnp.int32(0)` in the index map, shifts and
    # masks for `//` and `%`, and a semaphore a stream, not one indexed array.
    def kernel(starts_ref, *refs):
        srcs = refs[:n_streams]
        outs = refs[n_streams:2 * n_streams]
        bufs = refs[2 * n_streams:3 * n_streams]
        sems = refs[3 * n_streams:]
        base = pl.program_id(0) * BLOCK

        def plan(i):
            s = starts_ref[base + i]
            r = s >> 7              # s // LANES
            # the last row is read from only when the start is not at a
            # row's edge, and then it is inside the stream: clamp the copy,
            # not the read
            row0 = jnp.minimum(r, R - n_copy)
            return row0, r - row0, s & (LANES - 1)

        def copy(j, i, row0):
            return pltpu.make_async_copy(
                srcs[j].at[pl.ds(row0, n_copy)],
                bufs[j].at[i, pl.ds(0, n_copy)], sems[j])

        def start(i, carry):
            row0, _, _ = plan(i)
            for j in range(n_streams):
                copy(j, i, row0).start()
            return carry

        slots = (jnp.int32(0), jnp.int32(BLOCK))
        jax.lax.fori_loop(*slots, start, None)

        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)

        def finish(i, carry):
            row0, k, c = plan(i)
            spill = lane + c >= LANES       # these lanes are the next row's
            at = pl.multiple_of(i * rows, rows)
            for j in range(n_streams):
                copy(j, i, row0).wait()
                w = bufs[j][i, pl.ds(k, n_copy), :]
                w = pltpu.roll(w, (LANES - c) & (LANES - 1), 1)
                outs[j][pl.ds(at, rows), :] = jnp.where(
                    spill, w[1:n_copy], w[0:rows])
            return carry

        jax.lax.fori_loop(*slots, finish, None)

    # n_copy + 1 rows: a clamped copy is read from its second row on
    buf_rows = -(-(n_copy + 1) // 8) * 8
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(N // BLOCK,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_streams,
        out_specs=[pl.BlockSpec((BLOCK * rows, LANES),
                                lambda b, starts: (b, jnp.int32(0)))]
        * n_streams,
        scratch_shapes=[pltpu.VMEM((BLOCK, buf_rows, LANES), x.dtype)
                        for x in streams]
        + [pltpu.SemaphoreType.DMA(())] * n_streams)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, interpret=interpret,
        name="packed_slot_gather",
        out_shape=[jax.ShapeDtypeStruct((N * rows, LANES), x.dtype)
                   for x in streams])(
        starts, *[x.reshape(R, LANES) for x in streams])
    return tuple(x.reshape(N, chunk) for x in out)
