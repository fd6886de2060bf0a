"""Decode-step update of the power-retention state, as a Pallas kernel.

One token per row (``modules/power_retention.power_step``), per KV head:

    S <- exp(lg) S + phi(k) v^T,      num[h] = phi(q[h])^T S     (the group's query heads)

on the STACKED state ``(L, slots, G, D, d)`` float32 of every layer, at one
layer, in place (``input_output_aliases``). A KV head's state is ``D x d`` =
8704 x 128 float32 = 4.46 MB, sixty-eight times a KDA head's: it does not fit
a block, so the grid is ``(rows, KV heads, tiles of D)`` and a tile is 34
block pairs = 2176 rows = 1.1 MB. Each tile is read once and written once,
and the read-out of ALL the group's query heads (GQA inside a state kernel:
one state a KV head, ``n_rep`` readers) is taken from the tile while it is in
VMEM, accumulated over the tiles of a head in the revisited output block.

``phi`` is never in HBM. In the layout held (``modules/power_retention.phi``)
a tile row is ``(pair (I, J), a, b)`` and its entry ``c u[8I + a] u[8J + b]``:
one (8, d) register of the state is one ``(pair, a)``, its sublanes ``b``. So

    register <- exp(lg) register + k[8I + a] * (c k[8J : 8J + 8] (outer) v)
    T[h]     += q[h, 8I + a] * register            over the 8 values of a
    acc[h]   += (c q[h, 8J : 8J + 8]) * T[h]       once a pair

where ``k[8I + a]`` and ``q[h, 8I + a]`` are SCALARS (the row's vectors lie
in SMEM, ``scal``), and the ``8J`` slices are sublane columns broadcast along
the lanes once a pair (``cols``: the channels on the sublanes). Per register
of state: three vector operations for the update, two a query head for the
read-out, no lane reduction; the 8 -> 1 sublane sum is taken once a tile.

The normaliser ``z`` (``D`` numbers a head, 1/128 of the state) and the
division are XLA's: ``z <- exp(lg) z + phi(k)``, ``den = phi(q) . z``, ``y =
num / (den + eps)``.

A row that is not live costs no stream: its grid steps name the block of the
NEXT live step (or, after the last live row, of the last live step), so the
pipeline neither fetches nor writes anything for them, and the kernel body
does nothing there. With no live row at all every step names one block, which
is copied through once. ``reset`` rows start from zero: their tiles are not
read at all (a branch on the row's flag, not a product by 0: a non-finite
state must not survive it).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_inference_tpu.modules.power_retention import (
    BLOCK,
    normalised_read,
    pair_table,
    pairs_per_tile,
    phi,
)
from neuronx_distributed_inference_tpu.ops.row_modes import (
    LIVE as _LIVE,
    NONE_LIVE as _NONE_LIVE,
    TO_LAST as _TO_LAST,
    _row_modes,
)

#: block pairs a tile: 34 x 64 = 2176 rows of d float32, 1.1 MB in and as much
#: out (read on the chip, PR 66, a layer at 16 rows: 17 / 34 / 68 pairs a tile
#: 2.546 / 2.454 / 2.664 ms; the pair loop is unrolled, so compile time goes with it)
PAIRS_PER_TILE = 34


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _kernel(li_ref, mode_ref, er_ref, fresh_ref, pi_ref, pj_ref, scal_ref, cols_ref, v_ref,
            s_ref, y_ref, out_ref, *, n_rep, pp, d):
    r, g, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    mode = mode_ref[r]

    @pl.when((mode == _NONE_LIVE) & (r == 0) & (g == 0) & (t == 0))
    def _():
        out_ref[...] = s_ref[...]

    @pl.when((mode == _LIVE) & (t == 0))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    def advance(fresh: bool):
        """The tile's update and read-out; ``fresh``: the row starts from a
        zero state, so the tile is not read at all."""
        decay = scal_ref[n_rep + 1, 0]
        v8 = jnp.broadcast_to(v_ref[...], (BLOCK, d))

        def pair(p, acc):
            I, J = pi_ref[t * pp + p], pj_ref[t * pp + p]
            c = jnp.where(I == J, 1.0, 2.0 ** 0.5) * (float(d) ** -0.5)
            cols = cols_ref[pl.ds(pl.multiple_of(J * BLOCK, BLOCK), BLOCK), :] * c  # (8, 1 + n_rep)
            kv = cols[:, 0:1] * v8  # (8, d): c k[8J + b] v
            regs = []
            for a in range(BLOCK):
                at = pl.ds(pl.multiple_of((p * BLOCK + a) * BLOCK, BLOCK), BLOCK)
                reg = scal_ref[n_rep, I * BLOCK + a] * kv
                if not fresh:
                    reg = decay * s_ref[at, :] + reg
                out_ref[at, :] = reg
                regs.append(reg)
            out = []
            for h in range(n_rep):
                # pairwise sums: the chain an accumulator waits on is 3 adds long, not 8
                terms = [scal_ref[h, I * BLOCK + a] * regs[a] for a in range(BLOCK)]
                while len(terms) > 1:
                    terms = [terms[i] + terms[i + 1] for i in range(0, len(terms), 2)]
                out.append(acc[h] + cols[:, h + 1 : h + 2] * terms[0])
            return tuple(out)

        zero = tuple(jnp.zeros((BLOCK, d), jnp.float32) for _ in range(n_rep))
        # unrolled: the pairs' chains overlap (read on the chip, PR 66: a layer at 16
        # rows 3.59 ms as a loop, 2.55 unrolled)
        acc = jax.lax.fori_loop(0, pp, pair, zero, unroll=True)
        for h in range(n_rep):
            y_ref[h : h + 1, :] += jnp.sum(acc[h], axis=0, keepdims=True)

    # a select on the scalar, not a product by 0: a non-finite state does not survive a reset
    pl.when((mode == _LIVE) & (fresh_ref[r] != 0))(lambda: advance(True))
    pl.when((mode == _LIVE) & (fresh_ref[r] == 0))(lambda: advance(False))


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def power_state_update(
    s_stack: jax.Array,  # (L, R, G, D, d) float32: EVERY layer's S
    z_stack: jax.Array,  # (L, R, G, D) float32: every layer's normaliser
    layer_idx: jax.Array,  # int32 scalar
    q: jax.Array,  # (R, H, d) float32, normalised and rotated
    k: jax.Array,  # (R, G, d) float32, normalised and rotated
    v: jax.Array,  # (R, G, d)
    lg: jax.Array,  # (R, G) <= 0: log decay of each KV head
    valid: jax.Array,  # (R,) bool: False leaves the row's state as it is
    reset: jax.Array,  # (R,) bool: the row starts from a zero state
    *,
    eps: float = 1e-6,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (y (R, H, d) float32 — zero for a row that is not valid —, the
    two stacks with layer ``layer_idx`` advanced)."""
    L, R, G, D, d = s_stack.shape
    H = q.shape[1]
    n_rep = H // G
    f32 = jnp.float32
    I, J, _ = pair_table(d)
    pp = pairs_per_tile(d, PAIRS_PER_TILE)
    rows = pp * BLOCK * BLOCK
    T = len(I) // pp
    assert D == len(I) * BLOCK * BLOCK, (D, d)
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    qg = q.reshape(R, G, n_rep, d)
    fresh = reset & valid
    a = jnp.exp(jnp.where(valid[:, None], lg.astype(f32), 0.0))  # (R, G)
    li = jnp.reshape(layer_idx, (1,)).astype(jnp.int32)

    # the normaliser: D numbers a head, XLA's
    z = jax.lax.dynamic_index_in_dim(z_stack, li[0], 0, keepdims=False)
    z = jnp.where(fresh[:, None, None], 0.0, z)
    new_z = jnp.where(valid[:, None, None], a[..., None] * z + phi(k), z)
    # a product and a sum, not a matrix product: phi(q) is formed inside the reduction
    den = jnp.sum(phi(qg) * new_z[:, :, None, :], axis=-1)
    z_stack = jax.lax.dynamic_update_index_in_dim(z_stack, new_z, li[0], 0)

    # the row's vectors as scalars (rows: the group's q heads, k, [decay]) and
    # as sublane columns ([k | q heads], the channel on the sublanes)
    SR, CW, YR = _round_up(n_rep + 2, 8), _round_up(n_rep + 1, 8), _round_up(n_rep, 8)
    scal = jnp.concatenate(
        [qg, k[:, :, None], jnp.broadcast_to(a[:, :, None, None], (R, G, 1, d)),
         jnp.zeros((R, G, SR - n_rep - 2, d), f32)], axis=2)
    cols = jnp.concatenate([k[:, :, None], qg, jnp.zeros((R, G, CW - n_rep - 1, d), f32)], axis=2)
    cols = jnp.swapaxes(cols, 2, 3)  # (R, G, d, CW)
    mode, eff = _row_modes(valid)

    def state_at(r, g, t, li, mode, er, *_):
        m = mode[r]
        gg = jnp.where(m == _LIVE, g, jnp.where(m == _TO_LAST, G - 1, 0))
        tt = jnp.where(m == _LIVE, t, jnp.where(m == _TO_LAST, T - 1, 0))
        return li[0], er[r], gg, tt, 0

    def y_at(r, g, t, li, mode, er, *_):
        m = mode[r]
        return er[r], jnp.where(m == _LIVE, g, jnp.where(m == _TO_LAST, G - 1, 0)), 0, 0

    per_head = lambda shape, **kw: pl.BlockSpec(
        (None, None) + shape, lambda r, g, t, *_: (r, g, 0, 0), **kw)
    tile = pl.BlockSpec((None, None, None, rows, d), state_at)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(R, G, T),
        in_specs=[
            per_head((SR, d), memory_space=pltpu.SMEM),
            per_head((d, CW)),
            per_head((1, d)),
            tile,
        ],
        out_specs=[pl.BlockSpec((None, None, YR, d), y_at), tile],
    )
    num, s_stack = pl.pallas_call(
        functools.partial(_kernel, n_rep=n_rep, pp=pp, d=d),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, G, YR, d), f32),
            jax.ShapeDtypeStruct(s_stack.shape, s_stack.dtype),
        ],
        # operands: li, mode, eff, fresh, I, J, scal, cols, v, state -> outputs: num, state
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            # a row that is not live rides on its neighbour's block: in order
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="power_state_update",
    )(li, mode, eff, fresh.astype(jnp.int32), jnp.asarray(I), jnp.asarray(J),
      scal, cols, v[:, :, None, :], s_stack)
    y = normalised_read(num[:, :, :n_rep], den, eps)
    y = jnp.where(valid[:, None, None, None], y, 0.0)
    return y.reshape(R, H, d), s_stack, z_stack
