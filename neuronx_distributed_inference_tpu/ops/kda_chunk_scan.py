"""The sub-chunk recurrence of the chunked delta rule, as a Pallas kernel.

``modules/kda.chunk_operands`` turns a chunk of ``Q`` positions a row into
``n`` sub-chunks of ``c`` positions; what is left is sequential in the
sub-chunks, per row and head, from the state ``S (d_k, d_v)`` before the
chunk:

    V' = U_t - W_t S,   O_t = q_in_t S + P_t V',   S <- diag(e^{G_end_t}) S + k_out_t^T V'

``modules/kda.kda_chunk`` runs that as a ``lax.scan`` whose every step reads
the state of ALL the chunk program's rows from HBM and writes it back, around
a gather of the rows' state out of the stacked array and a scatter back. Here
it runs on the STACKED state ``(L, slots, heads, d_k, d_v)`` float32 in place
(``input_output_aliases``): grid ``(rows, heads / heads_per_block, tiles of
positions)``, a head's ``S`` (64 KiB at 128 x 128) is read once, the ``n``
sub-chunks run on it in VMEM (it stays in its output block across a row's
tiles of :data:`TILE_POSITIONS` positions: a chunk pass is one tile, a whole
prompt many), it is written once. The row's slot and the layer are scalar
prefetch: the kernel addresses ``(layer, slot)`` itself.

The products stay float32 at ``precision=HIGHEST``, as the scan's. ``W_t``
and ``q_in_t`` arrive stacked ``[W_t; q_in_t] (2c, d_k)``: one product with
``S`` gives both ``W_t S`` and ``q_in_t S``. The decays ``e^{G_end}`` scale
the KEY channel, which lies on the state's sublanes, so they arrive with
``d_k`` on the sublanes, ``(d_k, n)``, and a sub-chunk's column is taken out
by a masked sum over the lanes (exact).

A row with no valid position costs no stream (``ops/row_modes.py``): its
grid steps name the blocks of the next live step, the body does nothing
there, its state is untouched bit for bit and its slot, which may be out of
range, is never dereferenced; its ``o`` is never written and leaves as zeros
(a select in the wrapper). ``reset`` rows start from zero without their tile
being read (a branch, not a product by 0: a non-finite state must not
survive it). A partly valid row is correct by what ``chunk_operands`` feeds
past its valid prefix: ``g = 0``, ``b = 0``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_inference_tpu.modules.kda import chunk_operands
from neuronx_distributed_inference_tpu.ops.kda_state_update import pick_heads_per_block
from neuronx_distributed_inference_tpu.ops.row_modes import LIVE, NONE_LIVE, TO_LAST, _row_modes
from neuronx_distributed_inference_tpu.ops.tile_defaults import tile_default

#: the kernel's name: the pallas call's (a trace names the device op by it),
#: the tuning table's and the kernel registry's
KERNEL = "kda_chunk_scan"

#: heads a tile where the tuning table has no entry under the kernel's name
#: for the call's shape (PERF.md, PR 68, has the sweep: 2 / 4 / 8 heads a tile)
DEFAULT_HEADS_PER_BLOCK = 8

#: what a grid step's windows may hold of VMEM, both buffers of each (the
#: compiler's scoped default on a v5e is 16 MiB)
VMEM_BUDGET = 12 * 2**20

#: positions of a row a grid step holds in VMEM beside the state (a chunk
#: pass is one tile; a whole prompt walks its tiles with the state resident)
TILE_POSITIONS = 128

_HI = jax.lax.Precision.HIGHEST


def shape_class(Q: int, c: int, D: int) -> str:
    """The tuning table's key of a call: positions a row, sub-chunk, head_dim."""
    return f"q{Q}c{c}x{D}"


def tuned_heads_per_block(H: int, Q: int, c: int, D: int) -> int:
    """Heads a tile: the tuning table's under the kernel's name by the call's
    shape, else the default; no more than fit :data:`VMEM_BUDGET` (a head's
    windows, float32, two buffers each: the state in and out, ``[W; q_in]``,
    ``k_out``, ``U`` and ``o`` over a tile's positions, ``P`` and the decays on
    padded lanes); the most that divide ``H``."""
    want = tile_default(KERNEL, shape_class(Q, c, D), "float32", "heads", DEFAULT_HEADS_PER_BLOCK)
    Qt = sub_chunks_per_tile(-(-Q // c), c) * c
    a_head = 2 * 4 * (2 * D * D + 5 * Qt * D + (Qt + D) * 128)
    return pick_heads_per_block(H, max(1, min(want, VMEM_BUDGET // a_head)))


def sub_chunks_per_tile(n: int, c: int) -> int:
    """Sub-chunks a grid step runs: a tile of at most :data:`TILE_POSITIONS`
    positions, whole sub-chunks that divide the chunk's ``n``."""
    nt = max(1, min(n, TILE_POSITIONS // c))
    while n % nt:
        nt -= 1
    return nt


def _kernel(li_ref, mode_ref, slot_ref, row_ref, fresh_ref, wq_ref, u_ref, k_ref, p_ref, dec_ref,
            s_ref, o_ref, out_ref, *, hb, n, c):
    r, j, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    mode = mode_ref[r]
    first = (j == 0) & (t == 0)

    @pl.when((mode == NONE_LIVE) & (r == 0) & first)
    def _():
        out_ref[...] = s_ref[...]

    # the state enters its resident output block at the row's first tile of
    # positions: from zero without the tile being read where the row resets
    @pl.when((mode == LIVE) & (t == 0) & (fresh_ref[r] != 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((mode == LIVE) & (t == 0) & (fresh_ref[r] == 0))
    def _():
        out_ref[...] = s_ref[...]

    @pl.when(mode == LIVE)
    def _():
        def sub_chunk(u, carry):
            at = lambda rows: pl.ds(pl.multiple_of(u * rows, rows), rows)
            for i in range(hb):  # the heads' chains are independent: they overlap
                s = out_ref[i]  # (Dk, Dv)
                both = jnp.dot(wq_ref[i, at(2 * c), :], s, precision=_HI,
                               preferred_element_type=jnp.float32)  # [W_u S; q_in_u S]
                v_new = u_ref[i, at(c), :] - both[:c]
                o_ref[i, at(c), :] = both[c:] + jnp.dot(
                    p_ref[i, at(c), :], v_new, precision=_HI, preferred_element_type=jnp.float32)
                dec = dec_ref[i]  # (Dk, n)
                lane = jax.lax.broadcasted_iota(jnp.int32, dec.shape, 1)
                col = jnp.sum(jnp.where(lane == u, dec, 0.0), axis=1, keepdims=True)  # (Dk, 1)
                out_ref[i] = s * col + jax.lax.dot_general(
                    k_ref[i, at(c), :], v_new, (((0,), (0,)), ((), ())), precision=_HI,
                    preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, n, sub_chunk, 0)


def scan_on_stack(state, layer_idx, slots, operands, live, fresh, *, heads_per_block=None,
                  interpret=False):
    """The kernel's call: ``operands`` as ``modules/kda.chunk_operands``
    returns them, ``live`` / ``fresh`` (R,) bool (the row has a valid position;
    it starts from zero), ``slots`` (R,) int32. Returns (o (R, H, Q, Dv) with
    a dead row's block never written, the stacked state)."""
    W, U, q_in, k_out, P, G_end = operands
    L, S, H, Dk, Dv = state.shape
    R, _, n, c, _ = W.shape
    Q = n * c
    hb, nt = heads_per_block or tuned_heads_per_block(H, Q, c, Dk), sub_chunks_per_tile(n, c)
    assert H % hb == 0 and n % nt == 0, (H, hb, n, nt)
    J, T, Qt = H // hb, n // nt, nt * c
    wq = jnp.concatenate([W, q_in], axis=3).reshape(R, H, 2 * Q, Dk)
    # the key channel on the sublanes, a tile of positions' sub-chunks on the lanes
    dec = jnp.swapaxes(jnp.exp(G_end).reshape(R, H, T, nt, Dk), 3, 4)
    mode, eff = _row_modes(live)
    # the slot a row's steps name: its own where it is live, the next live
    # row's where it is not, slot 0 where no row is
    slot = jnp.where(mode == NONE_LIVE, 0, slots[eff])
    li = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)

    def of_live(r, mode, at, last):
        """A live row's own block index; a dead row's: the first of the next
        live row's, or the last of the last live row's."""
        m = mode[r]
        return jnp.where(m == LIVE, at, jnp.where(m == TO_LAST, last, 0))

    def row_at(r, j, t, li, mode, slot, row, fresh):
        return row[r], of_live(r, mode, j, J - 1), of_live(r, mode, t, T - 1), 0

    def dec_at(r, j, t, li, mode, slot, row, fresh):
        return row_at(r, j, t, li, mode, slot, row, fresh) + (0,)

    def state_at(r, j, t, li, mode, slot, row, fresh):
        return li[0], slot[r], of_live(r, mode, j, J - 1), 0, 0

    per_row = lambda rows, width: pl.BlockSpec((None, hb, rows, width), row_at)
    tile = pl.BlockSpec((None, None, hb, Dk, Dv), state_at)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(R, J, T),
        in_specs=[per_row(2 * Qt, Dk), per_row(Qt, Dv), per_row(Qt, Dk), per_row(Qt, c),
                  pl.BlockSpec((None, hb, None, Dk, nt), dec_at), tile],
        out_specs=[per_row(Qt, Dv), tile],
    )
    return pl.pallas_call(
        functools.partial(_kernel, hb=hb, n=nt, c=c),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, H, Q, Dv), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands: li, mode, slot, row, fresh, wq, U, k_out, P, dec, state -> outputs: o, state
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            # a row that is not live rides on its neighbour's blocks, and a
            # head's state stays in its output block across a row's tiles: in order
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=KERNEL,
    )(li, mode, slot, eff, fresh.astype(jnp.int32), wq, U.reshape(R, H, Q, Dv),
      k_out.reshape(R, H, Q, Dk), P.reshape(R, H, Q, c), dec, state)


@functools.partial(jax.jit, static_argnames=("chunk_size", "heads_per_block", "interpret"))
def kda_chunk_scan(
    state: jax.Array,  # (L, slots, H, Dk, Dv) float32: EVERY layer's state
    layer_idx: jax.Array,  # int32 scalar
    q: jax.Array,  # (R, Q, H, Dk) normalised and scaled
    k: jax.Array,  # (R, Q, H, Dk) normalised
    v: jax.Array,  # (R, Q, H, Dv)
    g: jax.Array,  # (R, Q, H, Dk) <= 0
    beta: jax.Array,  # (R, Q, H)
    valid: jax.Array,  # (R, Q) bool, a prefix of each row
    reset: jax.Array,  # (R,) bool: the row starts from a zero state
    slots: Optional[jax.Array] = None,  # (R,) int32: the rows' slots (None: row r owns slot r);
    # a row with no valid position may name a slot that is not there
    *,
    chunk_size: int = 16,
    heads_per_block: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """``modules/kda.kda_chunk`` on the stacked state: a chunk of Q positions
    per row (Q a multiple of the sub-chunk ``min(chunk_size, Q)``). Returns (o
    (R, Q, H, Dv) float32 — zeros for a row with no valid position —, the
    stacked state with layer ``layer_idx`` advanced at the live rows'
    slots)."""
    R, Q = valid.shape
    assert Q % min(int(chunk_size), Q) == 0, (Q, chunk_size)  # no padded sub-chunk: the gate's
    live = jnp.any(valid, axis=1)
    slots = jnp.arange(R, dtype=jnp.int32) if slots is None else slots.astype(jnp.int32)
    o, new = scan_on_stack(
        state, layer_idx, slots, chunk_operands(q, k, v, g, beta, valid, chunk_size), live,
        reset & live, heads_per_block=heads_per_block, interpret=interpret,
    )
    o = jnp.where(live[:, None, None, None], o, 0.0)  # a dead row's block was never written
    return jnp.swapaxes(o, 1, 2), new
