"""What a row's grid steps do in a state kernel whose rows are not all live
(``ops/power_state_update.py``, ``ops/kda_chunk_scan.py``).

A row that is not live costs no stream: its grid steps name the block of the
NEXT live step (or, after the last live row, of the last live step), so the
pipeline neither fetches nor writes anything for them, and the kernel body
does nothing there. With no live row at all every step names one block, which
is copied through once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: what a row's grid steps do (``mode``): advance its state; name the next
#: live step's block; name the last live step's block; (no live row) copy
#: one block through
LIVE, TO_NEXT, TO_LAST, NONE_LIVE = 0, 1, 2, 3


def _row_modes(live: jax.Array):  # the name ops carry in a program's text since PR 66: kept
    """(mode, effective row) of each row (:data:`LIVE` ...): a row that is
    not live names the next live row, else the last."""
    R = live.shape[0]
    idx = jnp.arange(R, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(live, idx, R), reverse=True)  # next live row at or after r
    last = jnp.max(jnp.where(live, idx, -1))
    mode = jnp.where(live, LIVE, jnp.where(nxt < R, TO_NEXT, TO_LAST))
    mode = jnp.where(last < 0, NONE_LIVE, mode)
    row = jnp.where(nxt < R, nxt, jnp.maximum(last, 0))
    return mode.astype(jnp.int32), row.astype(jnp.int32)
