"""Decode-step update of the Mamba-2 recurrent state, as a Pallas kernel.

One token per row:

    S <- exp(dt A) S + (dt x) (outer) B,        y = S . C

on the STACKED state ``(L, slots, heads, head_dim, state_size)`` float32 of
every state-space layer, at one layer. At 48 rows a decode step of
Granite-4.0-H-micro reads and writes 36 x 96 MiB of state; a carried buffer
that is copied once a layer is the whole step (PERF.md, PR 24). So the state
is aliased in and out (``input_output_aliases``): the kernel visits the
(row, head block) tiles of one layer, every other byte of the buffer stays
where it is, and the decode executable holds no state-sized copy
(tests/test_chip_compile.py pins that).

Grid ``(rows, heads / heads_per_block)``. A tile is ``(hb, P, N)`` float32
with the state size ``N`` on the lanes. The per-(head, p) scalars of the
update — ``dt x`` and the decay ``exp(dt A)`` — must then be broadcast along
lanes, so they arrive with ``P`` on the sublanes: one packed operand
``coef (rows, heads/hb, P, 2 hb)``, columns ``[0, hb)`` = ``dt x`` of the
block's heads, ``[hb, 2 hb)`` = their decays. ``B`` and ``C`` are lane
vectors. The two small operands cost ~3% of the state's bytes each at
``hb = 16``.

The read-out ``y = S . C`` sums over the LANES. It is one product on the
matrix unit, which this kernel leaves idle otherwise: ``C (groups a block,
N)`` against the tile's new state ``(hb P, N)``, both contracted over their
lanes at ``precision=HIGHEST`` (float32 in, float32 out), so ``y`` leaves as
one lane-dense row ``(1, hb P)`` a tile and is ``(rows, heads, P)`` outside by
a reshape. As 64 lane reductions and 16 selects a tile on the vector and
cross-lane units the kernel read 56% of 819 GB/s alone at Granite's shape;
with this read-out it reads 80%, which is what the tile copied in and out
with no arithmetic reads (PERF.md, PR 63). The tile's size moves nothing
between 512 KiB and 2 MiB; the heads a tile come from the tuning table by
the call's shape (``analysis/tuning_table.json``, 32 at both served shapes).

Validity: ``dt = 0`` for an invalid row (decay 1, increment 0), so its state
is rewritten bit for bit. ``reset`` rows start from zero (a select on the
loaded tile, not a product: a non-finite state must not survive it).

Groups of B/C (``n_groups`` G; Granite-4.0-H 1, ``nemotron_h`` 8): head
``h`` reads group ``h // (heads / G)``. A head block lies inside one group,
or covers whole groups (``hb = 16`` over two groups of 8 heads: two lane
vectors of B and of C a tile), so a tile's B and C are one block of
``(groups a block, N)``. Held to ``modules/ssm.mamba2_step``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_inference_tpu.ops import decode_attention as _da
from neuronx_distributed_inference_tpu.ops.tile_defaults import tile_default

KERNEL = "ssm_state_update"

#: heads per tile where the tuning table has no entry for the call's shape:
#: 16 x (64, 128) float32 = 512 KiB in, as much out
DEFAULT_HEADS_PER_BLOCK = 16


def _kernel(li_ref, reset_ref, coef_ref, b_ref, c_ref, s_ref, y_ref, out_ref, *, hb, hpg):
    r = pl.program_id(0)
    P, N = s_ref.shape[-2], s_ref.shape[-1]
    coef = coef_ref[...]  # (P, 2 hb)
    bs = b_ref[...]  # (groups a block, N)
    from_zero = jnp.full((P, N), reset_ref[r], jnp.int32) != 0
    for i in range(hb):
        g = i // hpg  # the block's group that head i reads
        b = bs if bs.shape[0] == 1 else bs[g : g + 1]
        s = jnp.where(from_zero, 0.0, s_ref[i])
        out_ref[i] = s * coef[:, hb + i : hb + i + 1] + coef[:, i : i + 1] * b
    # y = S . C of the whole tile in one product: (groups a block, hb P)
    y = jax.lax.dot_general(
        c_ref[...], out_ref[...].reshape(hb * P, N), (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    if y.shape[0] > 1:  # each head's lanes from the row of its own group
        row = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
        own = (lane >= row * (hpg * P)) & (lane < (row + 1) * (hpg * P))
        y = jnp.sum(jnp.where(own, y, 0.0), axis=0, keepdims=True)
    y_ref[...] = y


def pick_heads_per_block(num_heads: int, want: int = DEFAULT_HEADS_PER_BLOCK,
                         groups: int = 1) -> int:
    """The most heads a tile, at most ``want``, that divide ``num_heads`` and
    lie inside one group of B/C or cover whole groups."""
    hpg = num_heads // groups
    hb = min(want, num_heads)
    while num_heads % hb or (hb % hpg and hpg % hb):
        hb -= 1
    return hb


def heads_wanted(num_heads: int, groups: int, head_dim: int, state_size: int) -> int:
    """The heads a tile the tuning table gives the call's shape under the
    kernel's name (``h64g1x64x128``: Granite-4.0-H; ``h64g8x64x128``:
    ``nemotron_h``), ``DEFAULT_HEADS_PER_BLOCK`` where it has no entry;
    ``pick_heads_per_block`` then holds it to the kernel's rule."""
    shape_class = f"h{num_heads}g{groups}x{head_dim}x{state_size}"
    return tile_default(KERNEL, shape_class, "float32", "heads", DEFAULT_HEADS_PER_BLOCK)


@functools.partial(jax.jit, static_argnames=("heads_per_block", "interpret"))
def ssm_state_update(
    state: jax.Array,  # (L, R, H, P, N) float32: EVERY layer's state
    layer_idx: jax.Array,  # int32 scalar
    x: jax.Array,  # (R, H, P)
    B: jax.Array,  # (R, G, N), or (R, N): one group
    C: jax.Array,  # as B
    dt: jax.Array,  # (R, H) after softplus
    A: jax.Array,  # (H,) negative
    valid: jax.Array,  # (R,) bool: False leaves the row's state as it is
    reset: jax.Array,  # (R,) bool: the row starts from a zero state
    *,
    heads_per_block: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (R, H, P) float32 without the D skip, the stacked state with
    layer ``layer_idx`` advanced)."""
    L, R, H, P, N = state.shape
    G = B.shape[1] if B.ndim == 3 else 1
    hpg = H // G  # heads a group
    hb = heads_per_block or pick_heads_per_block(H, heads_wanted(H, G, P, N), groups=G)
    assert H % hb == 0 and H % G == 0 and (hb % hpg == 0 or hpg % hb == 0), (H, G, hb)
    J = H // hb
    gpb = max(1, hb // hpg)  # groups a head block covers
    n_gb = G // gpb  # group blocks a row

    def bc_block(r, j, li, rs):
        # rows and group blocks on one axis: G = gpb (one group block a row) is row r
        return (r if n_gb == 1 else r * n_gb + (j * hb) // (hpg * gpb), 0, 0)

    f32 = jnp.float32
    dt = jnp.where(valid[:, None], dt.astype(f32), 0.0)
    dA = jnp.exp(dt * A.astype(f32)[None, :])  # (R, H)
    dtx = dt[:, :, None] * x.astype(f32)  # (R, H, P)
    coef = jnp.concatenate(
        [
            jnp.transpose(dtx.reshape(R, J, hb, P), (0, 1, 3, 2)),
            jnp.broadcast_to(dA.reshape(R, J, 1, hb), (R, J, P, hb)),
        ],
        axis=-1,
    )  # (R, J, P, 2 hb)
    li = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)
    flags = (reset & valid).astype(jnp.int32)
    tile = pl.BlockSpec((None, None, hb, P, N), lambda r, j, li, rs: (li[0], r, j, 0, 0))
    # what a grid step holds in VMEM: the tile in and out and the small operands,
    # each twice (pipelined). Under the compiler's own scoped limit nothing is
    # asked; a tile past 2 MiB asks for what it needs, by decode_attention's rule
    held = 2 * 4 * (2 * hb * P * N + 2 * hb * P + 2 * gpb * N + hb * P)
    vmem_limit = None if held <= _da.SCOPED_VMEM_BYTES else held + 8 * 2**20
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, J),
        in_specs=[
            pl.BlockSpec((None, None, P, 2 * hb), lambda r, j, li, rs: (r, j, 0, 0)),
            pl.BlockSpec((None, gpb, N), bc_block),
            pl.BlockSpec((None, gpb, N), bc_block),
            tile,
        ],
        out_specs=[
            pl.BlockSpec((None, None, 1, hb * P), lambda r, j, li, rs: (r, j, 0, 0)),
            tile,
        ],
    )
    y, new = pl.pallas_call(
        functools.partial(_kernel, hb=hb, hpg=hpg),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, J, 1, hb * P), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands: li, flags, coef, B, C, state -> outputs: y, state
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
        name=KERNEL,
    )(li, flags, coef, B.astype(f32).reshape(R * n_gb, gpb, N),
      C.astype(f32).reshape(R * n_gb, gpb, N), state)
    return y.reshape(R, H, P), new
