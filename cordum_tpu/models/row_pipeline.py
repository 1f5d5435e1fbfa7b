"""The row pipeline under both recurrence kernels (``kda_step``,
``models/kda.py``; ``ssd_step``, ``models/ssd.py``; docs/SERVING.md §The
state slot): ONE Pallas TPU kernel body that carries each fed row's state
from the state array in HBM through VMEM and back, with the copies behind
the arithmetic.  A family brings its TOKEN BODY (one token's pass over a
row's state in VMEM) and nothing else.

**The work list.**  ``kda.state_rows`` compacts the step's fed table rows
(``n > 0``) to the front of ``StateRows.work`` and counts them
(``StateRows.fed``); both are prefetched into scalar memory with the rows'
``lo`` / ``n`` / ``slot`` / ``fresh``.  The grid is static, a step a table
row; grid step ``j`` advances work item ``j``, the steps behind the list's
count do nothing, and a row that feeds nothing is never looked at.

**The buffers.**  ``depth`` state buffers in VMEM (:func:`depth_for`: up to
:data:`BUFFERS`, as many as the kernel's budget holds beside its operands:
read from the shapes, never from a family) and a DMA semaphore a buffer and
direction.  Item ``j`` lives in buffer ``j % depth``.  Grid step ``j``:

1. item ``j + 1``'s buffer last held item ``j + 1 - depth``: that item's
   write-back is waited for (it was started ``depth - 1`` items ago), then
   item ``j + 1``'s read is started (a ``fresh`` row starts none);
2. item ``j``'s own read, started a step ago, is waited for (the first
   item's, started at grid step 0, is the only read nothing hides); a
   ``fresh`` row fills its buffer with zeros instead;
3. the row's tokens, one after the other, through the family's body;
4. item ``j``'s write-back is started and NOT waited for;
5. behind the last item every write-back still in flight is waited for, so
   the call returns with the state array whole.

So while a row's tokens run, the next fed row's state is on its way in and
the write-backs of the ``depth - 2`` rows before it are still on their way
out (at two buffers: none, the row before's write-back is what step 1 waits
for, the one copy of a row that stands in the open); a copy is waited for
only where its bytes are needed.

**What the pipeline relies on: a step never holds one slot in two fed
rows.**  A row's read is started before the rows ahead of it are written
back, so a second fed row of the same slot would read what the first has
not written yet.  A slot is a session's and a session is one table row
(``serving/engine.py`` ``state_allocator``), ``ServingBackend.step`` refuses
a step that names a slot twice, and ``kda.state_rows`` gives every row that
feeds nothing the null slot 0, which no fed row may name.

The module imports Pallas, so nothing imports it at its own import: the
kernels' ``rows_kernel`` do where a program is traced.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: state buffers the pipeline asks for where the budget holds them.  With
#: two, a row's write-back is the one copy that stands in the open (the next
#: read needs its buffer) and no two copies are ever in flight together; a
#: third hides it, but a read and a write in flight together share the HBM's
#: bandwidth and the row is then bound by both copies' bytes.  Measured on the
#: chip at the two cells' shapes (PERF.md section 6, PR 43): a decode row of
#: ``ssd_step`` (8 MiB of copies) 17.7 us unpipelined, 13.2 at two buffers,
#: 13.6 at three and at four; of ``kda_step`` (4 MiB) 12.8, 9.5, 8.6, 8.6:
#: within 3 % either way in the one cell the kernel binds, so the smaller
BUFFERS = 2
#: the scalar lists ahead of a kernel's operands (see :func:`advance_rows`)
N_LISTS = 6


def depth_for(state_bytes: int, operand_bytes: int, budget: int) -> int:
    """State buffers the pipeline gets: :data:`BUFFERS`, or as many as
    ``budget`` holds beside the operands (which Pallas' own pipeline holds
    twice: ``operand_bytes`` counts both)."""
    return int(min(BUFFERS, (budget - operand_bytes) // state_bytes))


def column(m: jax.Array, c: int) -> jax.Array:
    """``m[:, c:c + 1]`` as the primitive itself.  The token bodies bind
    ``jax.lax.slice`` / ``mul`` / ``add`` and not ``m[:, c:c + 1]`` / ``*`` /
    ``+``: the same equations in the kernel (its jaxpr text is hash-equal), half
    the Python to trace them, and an unrolled pass over a row's slabs is traced
    at every start-up (PERF.md section 6, PR 43)."""
    return jax.lax.slice(m, (0, c), (m.shape[0], c + 1))


def pipeline(work_ref, lo_ref, n_ref, slot_ref, fresh_ref, meta_ref, state_in, o_ref, state_out,
             bufs, sems, token: Callable[[Any, Any], None]) -> None:
    """The body of a recurrence kernel (the module docstring has the order of
    a grid step).  ``bufs`` ``[depth, *a row's state]`` and ``sems`` DMA ``[2,
    depth]`` (reads, write-backs) are the kernel's scratch; ``meta_ref`` holds
    ``(layer, fed)``; ``token(s_ref, at)`` advances the state in ``s_ref`` by
    the token in buffer slot ``at`` and writes its ``o_ref[at]``."""
    j = pl.program_id(0)
    layer, fed = meta_ref[0], meta_ref[1]
    depth = bufs.shape[0]

    def carried(i):  # item i starts from its slot's state, not from zeros
        return fresh_ref[work_ref[i]] == 0

    def read(i):
        at = i % depth
        return pltpu.make_async_copy(state_in.at[layer, slot_ref[work_ref[i]]], bufs.at[at],
                                     sems.at[0, at])

    def write(i):
        at = i % depth
        return pltpu.make_async_copy(bufs.at[at], state_out.at[layer, slot_ref[work_ref[i]]],
                                     sems.at[1, at])

    @pl.when(j == 0)
    def _():  # buffer slots no row feeds read zeros, not what VMEM held
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

        @pl.when((fed > 0) & carried(0))
        def _():
            read(0).start()

    @pl.when(j < fed)
    def _():
        @pl.when(j + 1 < fed)
        def _():
            @pl.when(j + 1 >= depth)
            def _():
                write(j + 1 - depth).wait()

            @pl.when(carried(j + 1))
            def _():
                read(j + 1).start()

        row = work_ref[j]
        s_ref = bufs.at[j % depth]

        @pl.when(carried(j))
        def _():
            read(j).wait()

        @pl.when(jnp.logical_not(carried(j)))
        def _():
            s_ref[...] = jnp.zeros(s_ref.shape, s_ref.dtype)

        lo = lo_ref[row]

        def one(t, carry):
            token(s_ref, lo + t)
            return carry

        jax.lax.fori_loop(0, n_ref[row], one, 0)
        write(j).start()

        @pl.when(j == fed - 1)
        def _():
            def drain(i, carry):
                write(i).wait()
                return carry

            jax.lax.fori_loop(jnp.maximum(fed - depth, 0), fed, drain, 0)


def advance_rows(kernel: Callable[..., None], operands: Sequence[jax.Array], out_width: int,
                 state: jax.Array, layer: Any, rows: Any, *, name: str,
                 vmem_budget: int) -> tuple[jax.Array, jax.Array]:
    """One layer's recurrences through ``kernel`` under the pipeline:
    ``operands`` float32 ``[T, heads, *]`` whole in VMEM (fetched once: their
    block does not move), ``state`` ``[layers, slots, *a row's state]`` in HBM
    and updated in place (the result aliases it), ``rows`` the step's
    ``kda.StateRows``.  ``kernel(*refs)`` gets the :data:`N_LISTS` scalar
    lists, the operands, ``state`` in, the output ``[T, heads, out_width]``,
    ``state`` out, the buffers and the semaphores, and hands all but its
    operands to :func:`pipeline` with its token body."""
    t_buf, h = operands[0].shape[:2]
    o_shape = (t_buf, h, out_width)
    operand_bytes = 2 * 4 * (sum(math.prod(x.shape) for x in operands) + math.prod(o_shape))
    depth = depth_for(4 * math.prod(state.shape[2:]), operand_bytes, vmem_budget)
    if depth < 2:
        raise ValueError(f"the recurrence's kernel needs {operand_bytes} bytes of VMEM for a "
                         f"buffer of {t_buf} slots and two row states beside them: over "
                         f"{vmem_budget}")
    whole = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))  # noqa: E731
    o, state = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(o_shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=N_LISTS,
            grid=(rows.n.shape[0],),
            in_specs=[*(whole(x.shape) for x in operands), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(whole(o_shape), pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[pltpu.VMEM((depth, *state.shape[2:]), jnp.float32),
                            pltpu.SemaphoreType.DMA((2, depth))]),
        # ``state`` (behind the prefetched lists and the blocked operands) is
        # the second result
        input_output_aliases={N_LISTS + len(operands): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem_budget),
        name=name,
    )(rows.work, jnp.minimum(rows.lo, t_buf - 1), rows.n, rows.slot,
      rows.fresh.astype(jnp.int32), jnp.stack([jnp.asarray(layer, jnp.int32), rows.fed]),
      *operands, state)
    return o, state


__all__ = ["BUFFERS", "N_LISTS", "advance_rows", "column", "depth_for", "pipeline"]
