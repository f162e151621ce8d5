"""Paged latent attention for one token a slot, as an in-repo Pallas TPU
kernel.

What it replaces (`serving/decode.py:live_page_attention` under
`serving/latent.py`'s page partial): a loop that gathers 64 pages of the
pool into a copy, scores them, writes a float32 partial a page and head
into buffers zeroed for it, and a pass that reads the buffers back by slot.
On a v5e that traffic was 3.7 ms of a 17.5 ms launch for 0.4 ms of rows
(PERF.md, PR 37). Here a slot's pages are read from the pool WHERE THEY LIE:
the pool stays in HBM, the kernel walks the step's list of live pages
(`page_walk`), brings each `[row, page]` page to VMEM by an async copy of
its own, some pages ahead of the one being scored, across the slots'
borders (the mean slot holds two live pages: a pipeline that drained at
every slot would wait for a copy a slot), and keeps a slot's running
maximum, sum and weighted latent `[H, kv_rank]` in float32 scratch (online
softmax). This is one key for all heads: a page is scored against the 128
heads in one product and its first ``kv_rank`` rows are the values.

The kernel also WRITES the step's new rows (PERF.md, PR 40). A slot's new
position is a column of its last live page, and that page is the last the
slot's walk copies to VMEM: the kernel sets the column there, after the
copy's ``wait()`` and before the page is scored (the causal mask admits
it), and sends the page back to where it lies in the pool by an async copy
of its own. The pool is an output aliased to the input, so it is written in
place; the step threads it to the next layer. Which page: ``pidx[s]``, the
caller's, never the walk's. ``pidx[s] == 0`` (the scratch page) is a slot
that is not fed and writes nothing: a masked step's inactive slot walks its
table's first page at position 0, and inferring the target from the walk
would write column 0 of a live page. A page's write-back is waited for
before its entry of the ring is filled again, ``DEPTH`` pages on, and at the
grid's end. No other slot reads the written page in the same call (a
partial page is never shared: `serving/prefix_cache.py`; idle slots walk the
scratch page, which nobody writes). What it replaces: taking each fed slot's
page out of the pool, selecting the column in and scattering the whole page
back, in XLA, every layer (4.8 ms of a 26.5 ms launch at 40 layers: PERF.md,
PR 39); a scatter of the column alone turns the whole pool round.

Layouts: ``q [S, H, row]``, ``pool [L, pages, row, page]`` (a position is a
COLUMN of its page, latent over rotated key), ``rows [S, row]``, out
``[S, H, kv_rank]`` in the pool's dtype: each head's normalised weighted
latent, which the caller takes through its ``wv_b`` once a slot. Operands
in the pool's dtype, products accumulated in float32, the weights ``p``
rounded to the pool's dtype before the value product: the precision the
loop has.

Constraints (`available`): ``page % 128 == 0`` (a page's columns are the
lanes), ``row`` and ``kv_rank`` multiples of the dtype's sublane tile, the
heads a multiple of 8, the blocks within the VMEM budget. Callers take
`live_page_attention` otherwise (`kernels.decode_attention_route` counts the
decision). ``interpret=True`` runs the same kernel on the CPU: the tests
in tests/test_latent_decode.py use it, and tests/test_causal_lm.py compiles
it at the served cell's sizes for a described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# a slot's pages are reduced BLOCK at a time, and DEPTH pages are in flight
# or in use at once (more than BLOCK, or nothing is on its way while a
# block is scored). Settled on the chip (PERF.md, PR 37)
BLOCK = 2
DEPTH = 6
# the kernel's scoped VMEM: well under a v5e core's 128 MiB
_VMEM_BUDGET = 32 * 1024 * 1024


def _vmem_bytes(heads, row, page, kv_rank, itemsize):
    """What the kernel holds in VMEM: the ring of pages, q's, the new
    rows' (128 float32 columns) and the output's blocks twice (the grid's
    pipeline), the float32 accumulator and a page's scores and weights."""
    return (DEPTH * row * page * itemsize
            + 2 * heads * row * itemsize + 2 * row * 128 * 4
            + 2 * heads * kv_rank * itemsize
            + heads * kv_rank * 4 + 2 * heads * 128 * 4
            + 3 * heads * page * 4)


def available(heads, row, page, kv_rank, dtype) -> bool:
    """Whether the kernel takes a pool of pages ``[row, page]`` of
    ``dtype`` under ``heads`` heads: shapes the TPU's tiles hold as they
    lie, and blocks that fit its VMEM."""
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize not in (2, 4):
        return False
    sublanes = 8 * (4 // itemsize)
    if page % 128 or row % sublanes or kv_rank % sublanes or heads % 8 \
            or not 0 < kv_rank <= row:
        return False
    return _vmem_bytes(heads, row, page, kv_rank, itemsize) < _VMEM_BUDGET


def page_walk(pos, table, page):
    """The order in which a step's kernels walk the pool, once a step for
    all its layers: slot ``s`` at position ``pos[s]`` has ``count[s] =
    pos[s] // page + 1`` live pages, ``table[s, 0 .. count[s] - 1]``, and
    they lie in ``pages`` from ``first[s]`` on, slot after slot; ``total``
    is their number. An idle slot (a zero row of the table at position 0)
    walks the scratch page. All int32, for the kernel's scalar memory."""
    S, P = table.shape
    count = (pos // page + 1).astype(jnp.int32)
    ends = jnp.cumsum(count)
    first = ends - count
    j = jnp.arange(S * P, dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(j[:, None] >= ends[None, :], axis=1), S - 1)
    i = jnp.clip(j - first[slot], 0, P - 1)
    return {"pages": table[slot, i].astype(jnp.int32), "first": first,
            "count": count, "total": ends[-1:],
            "pos": pos.astype(jnp.int32)}


def _kernel(pages_ref, first_ref, count_ref, total_ref, pos_ref, pidx_ref,
            q_ref, row_ref, pool_ref, o_ref, pool_out, ring, sems, wsems,
            sent_ref, wpend_ref, m_ref, l_ref, acc_ref, *, layer, kv_rank,
            page, scale):
    s = pl.program_id(0)
    first, count, total = first_ref[s], count_ref[s], total_ref[0]
    last_pos, target = pos_ref[s], pidx_ref[s]

    def copy(j):
        k = lax.rem(j, DEPTH)
        return pltpu.make_async_copy(pool_ref.at[layer, pages_ref[j]],
                                     ring.at[k], sems.at[k])

    def write(k):
        """Ring entry ``k`` back to the page the slot writes (a wait only
        needs the semaphore and the size)."""
        return pltpu.make_async_copy(ring.at[k], pool_out.at[layer, target],
                                     wsems.at[k])

    def drain(k):
        """The write-back from ring entry ``k``, if one is on its way."""
        @pl.when(wpend_ref[k] != 0)
        def _():
            write(k).wait()
            wpend_ref[k] = 0

    def send_ahead(j, most=BLOCK):
        """Before page ``j`` is scored: the walk's pages up to ``j + DEPTH
        - 1`` are on their way (they and ``j`` fill the ring; what lay
        before ``j`` has been scored). ``sent_ref`` counts the copies
        started; ``most`` are missing at most, a block's pages. An entry
        whose page is still being written back is waited for first."""
        for _ in range(most):
            k = sent_ref[0]

            @pl.when((k < j + DEPTH) & (k < total))
            def _():
                drain(lax.rem(k, DEPTH))
                copy(k).start()
                sent_ref[0] = k + 1

    @pl.when(s == 0)
    def _():
        sent_ref[0] = 0
        for k in range(DEPTH):
            wpend_ref[k] = 0
        send_ahead(0, DEPTH)

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[...]                                      # [H, row]
    cols = lax.broadcasted_iota(jnp.int32, (q.shape[0], page), 1)

    def put_row(i, k):
        """Page ``i`` of the slot, in ring entry ``k``: where it is the
        slot's last and the slot is fed, its column of the slot's position
        takes the new row, and the page goes back to the pool. The row is
        lane ``s % 128`` of its block (`latent_page_attention`), turned to
        the column's lane; only the column's lane tile of the page is
        read and written."""
        @pl.when((i == count - 1) & (target != 0))
        def _():
            col = last_pos - i * page
            lane = lax.rem(col, 128)
            new = pltpu.roll(row_ref[...], lax.rem(lane - lax.rem(s, 128)
                                                   + 128, 128), 1)
            new = new.astype(ring.dtype)                # [row, 128]
            at = lax.broadcasted_iota(jnp.int32, new.shape, 1) == lane
            for t in range(page // 128):

                @pl.when(col // 128 == t)
                def _():
                    tile = pl.ds(t * 128, 128)
                    ring[k, :, tile] = jnp.where(at, new, ring[k, :, tile])

            write(k).start()
            wpend_ref[k] = 1

    def reduce(i, n):
        """The slot's pages ``i .. i + n - 1`` into its running softmax,
        as one block: their scores make one maximum and one rescaling of
        what is kept, and the products of one page run beside the
        exponentials of the other."""
        j = first + i
        send_ahead(j)
        scs, cbs = [], []
        for t in range(n):
            copy(j + t).wait()
            k = lax.rem(j + t, DEPTH)
            put_row(i + t, k)
            cb = ring[k]                                # [row, page]
            sc = jnp.dot(q, cb, preferred_element_type=jnp.float32) * scale
            # causal + length: the columns up to the slot's position. A
            # live page holds one at least, so the maximum is finite
            scs.append(jnp.where(cols <= last_pos - (i + t) * page, sc,
                                 -jnp.inf))
            cbs.append(cb)
        m_prev = m_ref[...]                             # [H, 1]
        m_new = m_prev
        for sc in scs:
            m_new = jnp.maximum(m_new, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        l_new, acc = alpha * l_ref[...], alpha * acc_ref[...]
        for sc, cb in zip(scs, cbs):
            p = jnp.exp(sc - m_new)
            l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc + lax.dot_general(
                p.astype(cb.dtype), cb[:kv_rank], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # [H, kv_rank]
        m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc

    def block(b, carry):
        reduce(b * BLOCK, BLOCK)
        return carry

    lax.fori_loop(0, count // BLOCK, block, 0)
    for n in range(1, BLOCK):           # what is left of the slot's pages

        @pl.when(count % BLOCK == n)
        def _():
            reduce(count - n, n)

    @pl.when(s == pl.num_programs(0) - 1)
    def _():
        for k in range(DEPTH):
            drain(k)

    o_ref[...] = (acc_ref[...] * (1.0 / l_ref[...])).astype(o_ref.dtype)


def latent_page_attention(q, pool, walk, rows, pidx, *, layer, kv_rank,
                          scale, interpret=False):
    """``q [S, H, row]`` against each slot's own live pages of layer
    ``layer`` of ``pool [L, pages, row, page]``, in the order ``walk``
    (`page_walk`) gives, after each fed slot's new row ``rows[s]`` is set in
    the column of its position in its page ``pidx[s]`` (0: not fed, no
    write) -> (each head's weighted latent ``[S, H, kv_rank]`` in the pool's
    dtype, the pool so written). The pool is read in HBM as it lies, page
    by page, and written in place (the output is the input, aliased):
    nothing of it is copied or gathered beside the pages in flight and the
    pages written."""
    S, H, row = q.shape
    page = pool.shape[-1]
    kernel = functools.partial(_kernel, layer=int(layer), kv_rank=kv_rank,
                               page=page, scale=float(scale))
    by_slot = lambda s, *_: (s, 0, 0)  # noqa: E731
    # the rows as columns, 128 slots a block: slot s is lane s % 128 of
    # block s // 128 (a block of one column a slot would be padded to a
    # lane tile a slot, 128 times the row)
    groups = -(-S // 128)
    cols = jnp.pad(rows.astype(jnp.float32), ((0, groups * 128 - S), (0, 0))
                   ).reshape(groups, 128, row).swapaxes(1, 2)
    scalars = (walk["pages"], walk["first"], walk["count"], walk["total"],
               walk["pos"], pidx.astype(jnp.int32))
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((S, H, kv_rank), pool.dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(S,),
            in_specs=[pl.BlockSpec((None, H, row), by_slot),
                      pl.BlockSpec((None, row, 128),
                                   lambda s, *_: (s // 128, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec((None, H, kv_rank), by_slot),
                       pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[
                pltpu.VMEM((DEPTH, row, page), pool.dtype),
                pltpu.SemaphoreType.DMA((DEPTH,)),
                pltpu.SemaphoreType.DMA((DEPTH,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SMEM((DEPTH,), jnp.int32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, kv_rank), jnp.float32)]),
        # the pool, the operand after q and the rows' columns, is the
        # second output
        input_output_aliases={len(scalars) + 2: 1},
        # slot after slot: the ring of pages runs across their borders
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BUDGET),
        name="latent_page_attention",
        interpret=interpret,
    )(*scalars, q.astype(pool.dtype), cols, pool)
