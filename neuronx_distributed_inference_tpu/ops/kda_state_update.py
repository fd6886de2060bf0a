"""Decode-step update of the KDA (delta-rule) state, as a Pallas kernel.

One token per row (``modules/kda.kda_step``):

    S' = diag(a) S,      S <- S' + (b k) (v - S'^T k)^T,      o = S^T q

on the STACKED state ``(L, slots, heads, d_k, d_v)`` float32 of every KDA
layer, at one layer. The state is READ before it is written (``S'^T k``) and
its decay is a vector over the key channels: what ``ops/ssm_state_update``
(a scalar decay a head, no read) cannot express. A head's state is 64 KiB
at 128 x 128; 128 rows x 32 heads are 256 MiB read and as much written a
layer, the largest stream of a decode dispatch, so the state is aliased in
and out (``input_output_aliases``) and each (row, head block) tile is read
once and written once: the decode executable holds no state-sized copy
(tests/test_chip_compile.py pins that).

Grid ``(rows, heads / heads_per_block)``. A tile is ``(hb, d_k, d_v)``
float32 with the VALUE channels on the lanes. Everything indexed by the key
channel (the decay ``a``, ``k``, ``b k``, ``q``) is broadcast along lanes, so
it arrives with ``d_k`` on the sublanes in one packed operand ``coef (rows,
heads/hb, d_k, 4 hb)``: columns ``[0, hb)`` the decays of the block's heads,
then ``k``, ``b k`` and ``q``; ``v`` and ``o`` are lane rows ``(hb, d_v)``.
The two sums over the key channel are sublane reductions. The packed
operand is 3% of the state's bytes.

Validity: ``a = 1`` and ``b k = 0`` for an invalid row, so its state is
rewritten bit for bit. ``reset`` rows start from zero (a select on the
loaded tile, not a product: a non-finite state must not survive it).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: heads per tile: 16 x (128, 128) float32 = 1 MiB in, as much out
DEFAULT_HEADS_PER_BLOCK = 16


def _kernel(li_ref, reset_ref, coef_ref, v_ref, s_ref, o_ref, out_ref, *, hb):
    r = pl.program_id(0)
    Dk, Dv = s_ref.shape[-2], s_ref.shape[-1]
    coef = coef_ref[...]  # (Dk, 4 hb)
    vs = v_ref[...]  # (hb, Dv)
    from_zero = jnp.full((Dk, Dv), reset_ref[r], jnp.int32) != 0
    for i in range(hb):
        col = lambda part: coef[:, part * hb + i : part * hb + i + 1]  # (Dk, 1)
        s = jnp.where(from_zero, 0.0, s_ref[i]) * col(0)
        u = vs[i : i + 1] - jnp.sum(s * col(1), axis=0, keepdims=True)  # (1, Dv)
        new = s + col(2) * u
        out_ref[i] = new
        o_ref[i : i + 1, :] = jnp.sum(new * col(3), axis=0, keepdims=True)


def pick_heads_per_block(num_heads: int, want: int = DEFAULT_HEADS_PER_BLOCK) -> int:
    """The most heads a tile, at most ``want``, that divide ``num_heads``."""
    hb = min(want, num_heads)
    while num_heads % hb:
        hb -= 1
    return hb


@functools.partial(jax.jit, static_argnames=("heads_per_block", "interpret"))
def kda_state_update(
    state: jax.Array,  # (L, R, H, Dk, Dv) float32: EVERY layer's state
    layer_idx: jax.Array,  # int32 scalar
    q: jax.Array,  # (R, H, Dk) normalised and scaled
    k: jax.Array,  # (R, H, Dk) normalised
    v: jax.Array,  # (R, H, Dv)
    g: jax.Array,  # (R, H, Dk) <= 0: log decay of each key channel
    beta: jax.Array,  # (R, H)
    valid: jax.Array,  # (R,) bool: False leaves the row's state as it is
    reset: jax.Array,  # (R,) bool: the row starts from a zero state
    *,
    heads_per_block: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (o (R, H, Dv) float32, the stacked state with layer
    ``layer_idx`` advanced)."""
    L, R, H, Dk, Dv = state.shape
    hb = heads_per_block or pick_heads_per_block(H)
    assert H % hb == 0, (H, hb)
    J = H // hb
    f32 = jnp.float32
    live = valid[:, None, None]
    a = jnp.exp(jnp.where(live, g.astype(f32), 0.0))
    k = k.astype(f32)
    bk = jnp.where(live, beta.astype(f32)[..., None] * k, 0.0)
    # (R, H, Dk) x 4 -> (R, J, Dk, 4 hb): the key channel on the sublanes
    packed = jnp.stack([a, k, bk, q.astype(f32)], axis=1).reshape(R, 4, J, hb, Dk)
    coef = jnp.transpose(packed, (0, 2, 4, 1, 3)).reshape(R, J, Dk, 4 * hb)
    li = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)
    flags = (reset & valid).astype(jnp.int32)
    tile = pl.BlockSpec((None, None, hb, Dk, Dv), lambda r, j, li, rs: (li[0], r, j, 0, 0))
    row = pl.BlockSpec((None, hb, Dv), lambda r, j, li, rs: (r, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, J),
        in_specs=[
            pl.BlockSpec((None, None, Dk, 4 * hb), lambda r, j, li, rs: (r, j, 0, 0)),
            row,
            tile,
        ],
        out_specs=[row, tile],
    )
    o, new = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, H, Dv), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operands: li, flags, coef, v, state -> outputs: o, state
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="kda_state_update",
    )(li, flags, coef, v.astype(f32), state)
    return o, new
