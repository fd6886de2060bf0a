"""The paged index-score kernel (``ops/index_scores.py``) against the gathered
form it replaces on the chip (``read_stream_at_layer`` + ``index_scores``),
in interpret mode on the CPU at the published widths (32 index heads of 128,
pool blocks of 32), both programs' shape classes; its gate; and the serving
path through it, held to the benchmark's plain reference.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from neuronx_distributed_inference_tpu.modules import block_kvcache as bk
from neuronx_distributed_inference_tpu.modules import sparse_index
from neuronx_distributed_inference_tpu.ops import index_scores as ix

HEADS, DIM, BS = 32, 128, 32
GROUP = ix.GROUP_TOKENS
#: a table no group divides (2.06 groups of 1024): the last group is drawn back
MB = 66
W = MB * BS
#: an empty row, one key, a group's edge - 1 / + 0 / + 1, the whole bucket
FRONTIERS = [0, 1, GROUP - 1, GROUP, GROUP + 1, W, 2 * GROUP, 777]


def pool_and_table(rng, B, frontiers, dtype):
    """A pool whose blocks lie out of order in the rows' tables, garbage
    entries (block 0) in the tail past a row's frontier, and NaN in every
    pool position no row holds a live key at. Also the pool as the rows'
    live keys alone define it (zeros elsewhere), for the gathered form."""
    NB = B * MB
    pool = rng.standard_normal((2, NB + 1, 1, BS, DIM)).astype(np.float32)
    table = (1 + rng.permutation(NB)).reshape(B, MB).astype(np.int32)
    live = np.zeros((NB + 1, BS), bool)
    for b, f in enumerate(frontiers):
        for j in range(MB):
            n = min(max(f - j * BS, 0), BS)
            live[table[b, j], :n] = True
            if n == 0 and j % 2:
                table[b, j] = bk.GARBAGE_BLOCK
    planted = np.where(live[None, :, None, :, None], pool, np.nan)
    clean = np.where(live[None, :, None, :, None], pool, 0.0)
    return jnp.asarray(planted, dtype), jnp.asarray(clean, dtype), jnp.asarray(table)


def both_forms(B, Sq, frontiers, seed, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    planted, clean, table = pool_and_table(rng, B, frontiers, dtype)
    q = jnp.asarray(rng.standard_normal((B, Sq, HEADS, DIM)), dtype)
    w = jnp.asarray(rng.standard_normal((B, Sq, HEADS)), jnp.float32)
    li = jnp.int32(1)
    got = ix.paged_index_scores(
        q, w, planted, li, table, jnp.asarray(frontiers, jnp.int32), interpret=True
    )
    want = sparse_index.index_scores(q, w, bk.read_stream_at_layer(clean, li, table))
    live = np.arange(W)[None, None, :] < np.asarray(frontiers)[:, None, None]
    return np.asarray(got), np.asarray(want), np.broadcast_to(live, got.shape)


@pytest.mark.parametrize("B,Sq", [(32, 1), (8, 8), (8, 16), (8, 128)],
                         ids=["decode32x1", "chunk8x8", "chunk8x16", "chunk8x128"])
def test_the_kernel_scores_a_rows_live_keys_as_the_gathered_form_does(B, Sq):
    """Live scores equal to float32 tolerance (another order of the same sum
    over the heads) at both programs' shapes, over rows whose frontier is 0,
    1, a group's edge - 1 / + 0 / + 1 and the whole bucket, through a table
    out of order with garbage entries past the frontier and NaN in the pool
    wherever no live key lies: nothing past a row's frontier reaches a live
    score. What the kernel leaves past the frontier is unread here, as
    ``select`` leaves it."""
    frontiers = [FRONTIERS[i % len(FRONTIERS)] for i in range(B)]
    got, want, live = both_forms(B, Sq, frontiers, seed=67 + Sq)
    assert got.shape == want.shape == (B, Sq, W) and got.dtype == np.float32
    assert np.isfinite(got[live]).all()
    np.testing.assert_allclose(got[live], want[live], rtol=0, atol=2e-5 * np.abs(want[live]).max())


@pytest.mark.parametrize("B,Sq", [(32, 1), (8, 16)], ids=["decode32x1", "chunk8x16"])
def test_select_picks_the_same_keys_from_both_forms(B, Sq):
    """``select`` over the kernel's scores, NaN and stale columns past the
    frontier included, picks the set it picks from the gathered form's."""
    frontiers = [FRONTIERS[i % len(FRONTIERS)] for i in range(B)]
    got, want, live = both_forms(B, Sq, frontiers, seed=6700 + Sq)
    k = 256
    picked = [np.asarray(sparse_index.select(jnp.asarray(s), jnp.asarray(live), k)) for s in (got, want)]
    assert (picked[0] == picked[1]).all()
    counts = picked[0].sum(-1)
    assert (counts == np.minimum(np.asarray(frontiers), k)[:, None]).all()


def test_a_group_the_table_divides_and_float32_pools():
    """A table of whole groups (no group drawn back), a float32 pool of 8-row
    blocks and a speculation width that is no sublane tile (3 queries)."""
    rng = np.random.default_rng(5)
    B, Sq, Hn, bs, mb = 2, 3, 4, 8, 32
    pool = jnp.asarray(rng.standard_normal((1, B * mb + 1, 1, bs, DIM)), jnp.float32)
    table = jnp.asarray((1 + rng.permutation(B * mb)).reshape(B, mb), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, Sq, Hn, DIM)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, Sq, Hn)), jnp.float32)
    frontier = jnp.asarray([bs * mb, 9], jnp.int32)
    got = ix.paged_index_scores(q, w, pool, jnp.int32(0), table, frontier, interpret=True)
    want = sparse_index.index_scores(q, w, bk.read_stream_at_layer(pool, jnp.int32(0), table))
    np.testing.assert_allclose(got[0], want[0], rtol=0, atol=1e-4)
    np.testing.assert_allclose(got[1, :, :9], want[1, :, :9], rtol=0, atol=1e-4)


@pytest.mark.parametrize("why,pool_shape,dtype,kv_width,chip,admitted", [
    ("glm-5's stream at its three wide buckets", (5, 9, 1, 32, 128), jnp.bfloat16, 16896, True, True),
    ("", (5, 9, 1, 32, 128), jnp.bfloat16, 12288, True, True),
    ("", (5, 9, 1, 32, 128), jnp.bfloat16, 8192, True, True),
    ("off the chip", (5, 9, 1, 32, 128), jnp.bfloat16, 16896, False, False),
    ("a key off the lanes", (5, 9, 1, 32, 64), jnp.bfloat16, 16896, True, False),
    ("a block of half a sublane tile", (5, 9, 1, 8, 128), jnp.bfloat16, 8192, True, False),
    ("a width no block divides", (5, 9, 1, 32, 128), jnp.bfloat16, 8200, True, False),
    ("a width no lane row divides: 3 blocks of 32", (5, 9, 1, 32, 128), jnp.bfloat16, 96, True, False),
    ("a float32 pool of 8-row blocks", (5, 9, 1, 8, 128), jnp.float32, 128, True, True),
])
def test_the_gate_admits_what_the_kernel_serves(monkeypatch, why, pool_shape, dtype, kv_width, chip, admitted):
    monkeypatch.setattr(ix, "on_tpu", lambda: chip)
    stream = jax.ShapeDtypeStruct(pool_shape, dtype)
    assert ix.use_index_kernel(stream, kv_width) is admitted


def test_the_walk_is_counted_by_the_kernels_own_group(monkeypatch):
    """``index_blocks_walked``: whole groups of the ``P`` the call computes up
    to each row's frontier, the table's width at most (the drawn-back last
    group scores no entry twice in the count), nothing for an empty row;
    every entry of every row's table where the gate refuses the call."""
    P = ix.blocks_per_group(BS, DIM, jnp.bfloat16, 528)
    assert P == GROUP // BS == 32
    keys = jax.ShapeDtypeStruct((5, 9, 1, BS, DIM), jnp.bfloat16)
    monkeypatch.setattr(ix, "on_tpu", lambda: True)
    assert ix.index_blocks_walked([0, 1, 32, 33, 500, 528], 528, keys) == 0 + 32 + 32 + 64 + 512 + 528
    assert ix.index_blocks_walked([5], 16, keys) == 16  # a table narrower than a group: P = 16
    monkeypatch.setattr(ix, "on_tpu", lambda: False)  # the bucket is gathered: every entry
    assert ix.index_blocks_walked([0, 1, 528], 528, keys) == 3 * 528


def test_the_serving_path_scores_through_the_kernel_and_counts_its_walk(monkeypatch):
    """Past ``index_topk``, at a kv width the gate admits, both step programs
    score through ``paged_index_scores`` (interpret mode here; groups of 8
    blocks, so the longer prompt walks two of a 16-block table): two requests
    of unlike lengths beside two idle rows give the reference's logits. At
    the narrower bucket the gate refuses (64 keys are half a lane row) and
    the gathered form answers. ``nxdi_index_key_blocks_total`` counts, a
    layer with an indexer, the whole groups up to each live row's frontier as
    walked where the kernel runs (every entry of a live row's table where it
    does not) and the rest of the bucket's width over every row of the pass's
    dispatches as skipped."""
    from benchmark.harness.references import glm_dsa as ref
    from neuronx_distributed_inference_tpu.ops.tile_defaults import tile_overrides
    from neuronx_distributed_inference_tpu.runtime.serving import ServingSession
    from neuronx_distributed_inference_tpu.telemetry import TelemetrySession
    from tests.conftest import LogitSpy, drain
    from tests.test_glm_dsa_reference import KERNEL_MODEL, VOCAB, assert_is_the_reference, make_app

    monkeypatch.setattr(ix, "on_tpu", lambda: True)
    widths = []
    kernel = ix.paged_index_scores

    def scorer(q_i, w, cache, li, table, frontier, **k):
        widths.append((q_i.shape[1], table.shape[1] * cache.shape[3]))
        return kernel(q_i, w, cache, li, table, frontier, **k)

    monkeypatch.setattr(ix, "paged_index_scores", scorer)
    model = dict(KERNEL_MODEL, index_head_dim=128)
    chunk, bs, L = 32, 16, model["num_hidden_layers"]
    served = make_app(model=model, chunk=chunk, pa_block_size=bs, pa_num_blocks=40, seq_len=256,
                      token_generation_buckets=[16, 64, 256])
    geo = ref.geometry(model, 1)
    rng = np.random.default_rng(29)
    prompts = [rng.integers(0, VOCAB, size=n) for n in (5 * chunk + 8, 2 * chunk)]
    served.init_kv_cache()
    tel = TelemetrySession(enabled=True)
    passes = []
    with tile_overrides(ix.KERNEL, {"pages": 8}), LogitSpy(served) as spy:
        s = ServingSession(served, telemetry=tel)
        count = s._count_pass

        def count_pass(program, shape, rows, tokens, dispatches, **k):
            passes.append((program, shape[0] * dispatches, k["kv_width"], list(k["spans"])))
            return count(program, shape, rows, tokens, dispatches, **k)

        monkeypatch.setattr(s, "_count_pass", count_pass)
        for i, p in enumerate(prompts):
            assert s.add_request(f"r{i}", p, max_new_tokens=3)
        drain(s)
        for i, p in enumerate(prompts):
            generated = [int(t) for t in s.requests[f"r{i}"].generated]
            positions = [len(p) - 1 + k for k in range(3)]
            want = ref.reference_logits(served.params, geo, list(p) + generated[:-1], positions)
            got = np.stack([spy.at(i, q) for q in positions]).astype(np.float32)
            assert_is_the_reference(got, want)
    snap = tel.registry.snapshot()["nxdi_index_key_blocks_total"]["samples"]
    tel.stop()
    # traced once a program: a chunk and a decode program at the bucket the gate admits, none under it
    assert {w for _, w in widths} == {256} and {q for q, _ in widths} >= {1, chunk}
    got = {(x["labels"]["program"], x["labels"]["kind"]): x["value"] for x in snap}
    want = {}
    for program, rows, width, spans in passes:
        if width <= model["index_topk"]:
            continue
        mb = width // bs
        live = [-(-(first + n) // bs) for first, n in spans]
        walked = sum(-(-n // 8) * 8 for n in live) if width == 256 else mb * len(live)
        want[program, "walked"] = want.get((program, "walked"), 0) + L * walked
        want[program, "skipped"] = want.get((program, "skipped"), 0) + L * (rows * mb - walked)
    assert got == want and set(got) == {(p, k) for p in ("chunk", "decode") for k in ("walked", "skipped")}
    assert any(width == 256 and max(first + n for first, n in spans) > 128 for _, _, width, spans in passes)
