"""The session's side of a decode step that fills a BLOCK of positions
(``models/base.BlockStepSpec``; the device's side is ``models/base.forward``
with ``block_reveal``).

A row generates block by block. The prompt's whole blocks are prefilled by
the chunk program; what is left of the prompt opens the first block, and a
block's other positions start as the mask token. A DENOISE pass runs the
block's positions against the blocks before them, predicts a token and a
confidence AT each masked position and reveals the ``per_pass`` most
confident (on the device: the next pass's ids come back as ``next_ids``).
When no mask is left a COMMIT pass runs the block once more; every pass
writes its K and V before it attends, so the commit's stay. The schedule is
the host's to know without a fetch: a block with ``m`` masks takes
``ceil(m / per_pass)`` denoise passes, then the commit.

Here: a row's block in progress (:class:`Block`), which pass comes next and
on which ids (:class:`BlockRows.plan`), and what a fetched pass reveals or
commits (:meth:`BlockRows.consume`). ``runtime/serving.py`` dispatches and
fetches; ``Request.pos`` stays the first position of the block in progress
and ``Request.generated`` grows only at a commit.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np


@dataclass
class Block:
    """One block of one row, from its first pass to its commit."""

    start: int  # first position
    ids: np.ndarray  # (B,) as the host last knew them: tokens and masks
    known: int  # leading positions the prompt filled
    denoise: int  # denoise passes the schedule gives it
    epoch: int  # the request's epoch when the block opened
    dispatched: int = 0  # passes dispatched (the commit is pass ``denoise``)
    consumed: int = 0  # passes fetched
    revealed_at: List[int] = field(default_factory=list)  # per position; -1: there before

    @property
    def commit_dispatched(self) -> bool:
        return self.dispatched > self.denoise


class BlockRows:
    """The blocks in progress of a session's rows (``Request.block``)."""

    def __init__(self, block_step, pos_limit: int):
        self.length = block_step.block_length
        self.per_pass = block_step.per_pass
        self.mask_id = block_step.mask_token_id
        self.pos_limit = pos_limit  # one past the last writable position

    def prefill_end(self, prompt_len: int) -> int:
        """The prompt tokens the chunk program carries: its whole blocks."""
        return prompt_len // self.length * self.length

    def _open(self, req, start: int, tokens) -> Block:
        ids = np.full(self.length, self.mask_id, np.int32)
        ids[: len(tokens)] = tokens
        masks = self.length - len(tokens)
        return Block(
            start=start, ids=ids, known=len(tokens), denoise=-(-masks // self.per_pass),
            epoch=req.epoch, revealed_at=[-1] * self.length,
        )

    def plan(self, reqs) -> Tuple[list, List[int]]:
        """What this step dispatches: ``rows`` = [(req, the block's first
        position)], and the slots whose ids are still on the device (the
        row's pass before is dispatched and not fetched). A row whose commit
        is in flight opens its next block on masks alone unless the commit ends it (budget or room: host-known; an EOS
        among the committed tokens is seen at the fetch, and the pass ahead
        of it is thrown away)."""
        rows, chained = [], []
        for req in reqs:
            block = req.block
            if block is None or block.epoch != req.epoch:
                # first block: what is left of the prompt opens it
                block = req.block = self._open(req, req.pos, req.input_ids[req.pos:])
            elif block.commit_dispatched:
                # the commit's tokens are in flight, or (a session that
                # consumes what it dispatches, async_mode off) already fetched
                # and in ``generated``
                in_flight = block.consumed <= block.denoise
                ahead = len(req.generated) + (self.length - block.known if in_flight else 0)
                start = block.start + self.length
                if ahead >= req.max_new_tokens or start + self.length > self.pos_limit:
                    continue  # the commit in flight ends the request
                block = req.block = self._open(req, start, ())
            rows.append((req, block.start))
            if block.dispatched > block.consumed:
                chained.append(req.slot)
        return rows, chained

    def ids(self, rows, num_slots: int) -> np.ndarray:
        """(slots, B) ids of this step's passes as the host knows them."""
        out = np.zeros((num_slots, self.length), np.int32)
        for req, _ in rows:
            out[req.slot] = req.block.ids
        return out

    def dispatched(self, rows) -> list:
        """Note the passes as dispatched; the snapshot's extra per row:
        (block, the pass's ordinal within it)."""
        extra = []
        for req, _ in rows:
            extra.append((req.block, req.block.dispatched))
            req.block.dispatched += 1
        return extra

    def consume(self, req, block: Block, ordinal: int, next_ids: np.ndarray) -> Optional[List[int]]:
        """A fetched pass of ``block``: positions that lost their mask were
        revealed by pass ``ordinal``. Returns None for a denoise pass, and
        for the commit the block's new tokens (cut at the budget and after
        an EOS), with ``req.revealed_at`` extended as ``req.generated`` will
        be. A block committed with a mask in it is a program error."""
        was_mask = block.ids == self.mask_id
        for j in np.flatnonzero(was_mask & (next_ids != self.mask_id)):
            block.revealed_at[j] = ordinal
        block.ids = np.asarray(next_ids, np.int32).copy()
        block.consumed = ordinal + 1
        if ordinal < block.denoise:
            return None
        if (block.ids == self.mask_id).any() or min(block.revealed_at[block.known:], default=0) < 0:
            raise RuntimeError(
                f"{req.req_id}: the block at {block.start} was committed with a mask token in it "
                f"(ids {block.ids.tolist()}, schedule {block.denoise} denoise passes)"
            )
        tokens = [int(t) for t in block.ids[block.known:]]
        room = req.max_new_tokens - len(req.generated)
        if req.eos_token_id is not None and req.eos_token_id in tokens:
            room = min(room, tokens.index(req.eos_token_id) + 1)
        tokens = tokens[:room]
        req.revealed_at.extend(block.revealed_at[block.known : block.known + len(tokens)])
        return tokens
