"""``y[rows[i]] += updates[i]`` for row indices that are unique inside each
block of ``block`` consecutive ones: a Mosaic call of row DMAs.

XLA:TPU's scatter-add is the wrong tool for the routed layer's combine
(``models/deepseek.py``). Read on a v5e into ``[32,768, 2,048]`` float32 (PR
45, ``PERF.md`` section 6): 0.27 microseconds a row at 256 rows, a
millisecond and more a call from some thousands of rows on whatever their
number, declared sorted and unique or not; a row GATHER of the same bytes
costs 0.03. A scatter-add of unique rows is two row copies with an add
between them, and this call is that: a grid step owns ``block`` indices,
starts one HBM -> VMEM copy a live row of ``y``, waits for them all, adds the
step's block of ``updates`` (brought in by its ``BlockSpec``), starts one
VMEM -> HBM copy a row back and waits. ``y`` is aliased to the result and
never passes through VMEM whole; the steps run in order.

The caller's promise: inside one block no row index repeats (the routed
layer's blocks are tiles of ONE expert, and a token picks an expert once).
The same row in two blocks is fine: a step ends with its writes done. An
index at or past ``y``'s rows is dead: nothing is read or written for it.

A copy may not slice ONE row out of ``[N, width]``: float32 lies there in
(8, 128) tiles, a row is an eighth of ``width / 128`` tiles. So the caller
keeps ``y`` and ``updates`` as ``[N, width / 128, 128]`` between calls
(``slab``): a row is then whole tiles, contiguous in HBM, and one row is a
slice of the leading axis. Two paths by the operands' shapes, no knob: the
Mosaic call for such slabs, ``y.at[rows].add`` for a width that is no
multiple of 128 (the toy widths of the tests), where the rows stay
``[N, width]``. On the CPU the call runs in interpret mode, as the other
kernels do (``_platform.interpret_default``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fl4health_tpu.kernels._platform import interpret_default

# row copies a loop trip starts or waits for: the scalar core's loop overhead
# is paid once a trip
_UNROLL = 8


def _kernel(rows_ref, y_in, upd_ref, y_out, buf, sems, *, block: int):
    # y, updates and buf are [rows, width / 128, 128]: a row is whole tiles
    del y_in  # aliased to y_out: one buffer, read and written through y_out
    n = y_out.shape[0]
    first = pl.program_id(0) * block

    def each_live_row(fn):
        def trip(j, carry):
            for u in range(_UNROLL):
                r = j * _UNROLL + u
                row = rows_ref[first + r]

                @pl.when(row < n)
                def _():
                    fn(row, r)
            return carry

        jax.lax.fori_loop(0, block // _UNROLL, trip, 0)

    def read(row, r):
        return pltpu.make_async_copy(y_out.at[pl.ds(row, 1)],
                                     buf.at[pl.ds(r, 1)], sems.at[0])

    def write(row, r):
        return pltpu.make_async_copy(buf.at[pl.ds(r, 1)],
                                     y_out.at[pl.ds(row, 1)], sems.at[1])

    each_live_row(lambda row, r: read(row, r).start())
    each_live_row(lambda row, r: read(row, r).wait())
    buf[...] = buf[...] + upd_ref[...]
    each_live_row(lambda row, r: write(row, r).start())
    each_live_row(lambda row, r: write(row, r).wait())


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _add_rows_call(y, rows, updates, *, block: int, interpret: bool):
    n_rows, *slab = updates.shape
    return pl.pallas_call(
        functools.partial(_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_rows // block,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((block, *slab), lambda i, rows: (i, 0, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((block, *slab), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        # operands count the scalar-prefetched rows: y is the second
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="add_rows",
    )(rows, y, updates)


def slab(width: int) -> tuple:
    """The trailing shape a caller keeps its rows in between calls: whole
    (8, 128) float32 tiles a row, ``[width / 128, 128]``, where the Mosaic
    call takes them (a row is then one contiguous piece of HBM, and a copy
    of ONE row is a slice of the leading axis: in ``[N, width]`` a row is an
    eighth of 16 tiles, which no copy may slice), the plain ``[width]``
    otherwise."""
    return (width // 128, 128) if width % 128 == 0 else (width,)


def add_rows(y, rows, updates, block: int):
    """``y`` [N, *slab(d)] float32 with ``updates`` [R, *slab(d)] added at
    ``rows`` [R] int32 (``R`` a multiple of ``block``, a multiple of 8; an
    index >= N adds nothing), no index twice inside a block of ``block``."""
    if y.ndim == 2:
        return y.at[rows].add(updates, mode="drop")
    return _add_rows_call(y, rows, updates, block=block,
                          interpret=interpret_default())
