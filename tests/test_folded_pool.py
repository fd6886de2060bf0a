"""A K/V pool whose head_dim divides the chip's 128 lanes holds ``g = 128 //
head_dim`` KV heads side by side in one pool row (``block_kvcache.kv_streams``,
PR 65): the pool every writer meets is a head_dim-128 pool of ``H_kv / g``
heads, the paged kernels attend it with the queries laid in their own head's
lanes (``fold_queries``), and what reads it outside the kernels unfolds it.

Oracles are independent of the pool: a NumPy loop over an UNFOLDED pool for
the writes, the native masked softmax over the rows' own tokens for the two
kernels. Kernels run in interpret mode, through their dispatchers."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from neuronx_distributed_inference_tpu.modules.attention import AttnSpec, attention_decode
from neuronx_distributed_inference_tpu.modules.block_kvcache import (
    fold_queries,
    heads_a_row,
    init_block_cache,
    kv_block_bytes,
    kv_streams,
    read_block_cache_at_layer,
    unfold_outputs,
    update_block_cache_at_layer,
    write_form,
)
from neuronx_distributed_inference_tpu.ops.decode_attention import dispatch_paged_tkg_decode
from neuronx_distributed_inference_tpu.ops.paged_flash_attention import dispatch_paged_flash

# (H_kv, head_dim) -> heads a pool row; the last three are the controls: on
# the lanes already, an odd head count, a head_dim that fills no row
SHAPES = {"8x64": (8, 64, 2), "2x64": (2, 64, 2), "4x32": (4, 32, 4),
          "8x128": (8, 128, 1), "3x64": (3, 64, 1), "8x96": (8, 96, 1)}
L, NB, BS = 3, 24, 32


def _unfold(pool, g):
    """(L, NB+1, H / g, bs, g x D) -> (L, NB+1, H, bs, D), in NumPy."""
    pool = np.asarray(pool.astype(jnp.float32))
    l, nb, h, bs, w = pool.shape
    return pool.reshape(l, nb, h, bs, g, w // g).transpose(0, 1, 2, 4, 3, 5).reshape(l, nb, h * g, bs, w // g)


def _junk_pool(H, D, rng):
    cache = init_block_cache(L, NB, BS, H, D, dtype=jnp.bfloat16)
    return tuple(jnp.asarray(rng.normal(size=c.shape), jnp.bfloat16) for c in (cache.k, cache.v))


def _rows(S, rng):
    """(slots (3, S), block_table (3, 8)): consecutive positions of three
    sequences, as a pass of the split step brings them (so every form may
    write them): one from 8 tokens into a block (its first and, wider than a
    block, last block merged with what the pool holds), one from a block
    boundary that ends mid-block, one that sits the pass out (dropped)."""
    pages = rng.permutation(np.arange(1, NB + 1))[:16].reshape(2, 8)
    table = np.zeros((3, 8), np.int32)
    table[:2] = pages
    slots = np.full((3, S), -1, np.int64)
    for row, (start, n) in enumerate(((40, S), (64, max(1, S - 28)))):
        pos = start + np.arange(n)
        slots[row, :n] = table[row, pos // BS] * BS + pos % BS
    return jnp.asarray(slots, jnp.int32), jnp.asarray(table)


def _loop_write(pool, new, layer, slots):
    out = pool.copy()
    for b, s in zip(*np.nonzero(np.asarray(slots) >= 0)):
        slot = int(slots[b, s])
        out[layer, slot // BS, :, slot % BS] = new[b, s]
    return out


WRITES = [(shape, S) for shape in SHAPES for S in (1, 16, 128)]


@pytest.mark.parametrize("shape,S", WRITES, ids=[f"{s}-S{q}" for s, q in WRITES])
def test_what_a_pass_writes_reads_back_unfolded(shape, S):
    """Every form ``update_block_cache_at_layer`` takes at a pass's width (one
    token, a speculation width, a chunk whose edge blocks are merged) on a
    pool of ``g`` heads a row: the WHOLE pool, unfolded, is bit for bit what a
    loop writes into the unfolded pool, and ``read_block_cache_at_layer``
    gives the rows' blocks in token order at the model's ``(H_kv, D)``."""
    H, D, g = SHAPES[shape]
    rng = np.random.default_rng(S + H)
    k_pool, v_pool = _junk_pool(H, D, rng)
    assert k_pool.shape == (L, NB + 1, H // g, BS, g * D)
    slots, table = _rows(S, rng)
    k_new, v_new = (jnp.asarray(rng.normal(size=(3, S, H, D)), jnp.bfloat16) for _ in range(2))
    layer = 1
    k_up, v_up = update_block_cache_at_layer(k_pool, v_pool, k_new, v_new, jnp.int32(layer), slots)
    k_read, v_read = read_block_cache_at_layer(k_up, v_up, jnp.int32(layer), table, head_dim=D)
    assert k_read.shape == (3, 8 * BS, H, D)
    for pool, new, up, read in ((k_pool, k_new, k_up, k_read), (v_pool, v_new, v_up, v_read)):
        want = _loop_write(_unfold(pool, g), np.asarray(new.astype(jnp.float32)), layer, slots)
        np.testing.assert_array_equal(_unfold(up, g), want)
        gathered = want[layer][np.asarray(table)]  # (3, 8, H, bs, D)
        gathered = np.where((np.asarray(table) != 0)[:, :, None, None, None], gathered, 0.0)
        np.testing.assert_array_equal(
            np.asarray(read.astype(jnp.float32)),
            gathered.transpose(0, 1, 3, 2, 4).reshape(3, 8 * BS, H, D),
        )


@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_block_costs_what_it_cost_unfolded(shape):
    """``kv_block_bytes`` and the pool's own bytes are the parent's numbers:
    the fold moves heads into lanes and adds none."""
    H, D, g = SHAPES[shape]
    assert heads_a_row(H, D) == g
    assert kv_block_bytes(L, BS, H, D) == L * BS * 2 * H * D * 2
    streams = kv_streams(H, D)
    assert [(s.heads, s.width) for s in streams] == [(H // g, g * D)] * 2
    assert kv_block_bytes(L, BS, streams=streams) == kv_block_bytes(L, BS, H, D)
    cache = init_block_cache(L, NB, BS, H, D)
    assert cache.k.nbytes + cache.v.nbytes == (NB + 1) * kv_block_bytes(L, BS, H, D)
    # a pair never straddles a head shard, and a quantised pool keeps a head a row
    assert heads_a_row(H, D, shards=H) == 1
    assert heads_a_row(H, D, quantised=True) == 1
    assert init_block_cache(L, NB, BS, H, D, dtype=jnp.int8).k.data.shape[2:] == (H, BS, D)


# what the parent answered at (q_len 1 on the kernel, q_len 128) for the controls
PARENT_FORMS = {"8x128": ("kernel", "blocks"), "3x64": ("per_head", "per_head"),
                "8x96": ("per_head", "window")}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_write_form_is_asked_at_the_pool_rows_shape(shape):
    """Asked with the pool row's width and head count, a folded pool takes the
    in-kernel write of a one-token decode pass and whole blocks of a chunk
    pass, as any head_dim-128 pool; the controls keep the parent's answers."""
    H, D, g = SHAPES[shape]
    _, _, heads, _, width = init_block_cache(L, NB, BS, H, D).k.shape
    got = (write_form(1, width, heads, kernel_runs=True), write_form(128, width, heads))
    assert got == (("kernel", "blocks") if g > 1 else PARENT_FORMS[shape])
    # speculation widths and a run with the kernels off keep the per-head scatter
    assert write_form(16, width, heads, kernel_runs=True) == "per_head"
    assert write_form(1, width, heads, kernel_runs=False) == "per_head"


@pytest.mark.parametrize("g,n_rep", [(2, 4), (2, 1), (4, 2), (1, 3)])
def test_queries_lie_in_their_own_heads_lanes(g, n_rep):
    """``fold_queries``: query head ``h`` keeps its numbers in lane group
    ``(h // n_rep) % g`` and zeros elsewhere, in the order the heads had;
    ``unfold_outputs`` cuts the same group back."""
    D, Hq = 8, 2 * g * n_rep
    q = jnp.arange(1, 3 * Hq * D + 1, dtype=jnp.float32).reshape(3, Hq, D)
    laid = np.asarray(fold_queries(q, g, n_rep))
    assert laid.shape == (3, Hq, g * D)
    for h in range(Hq):
        group = (h // n_rep) % g
        lanes = slice(group * D, (group + 1) * D)
        np.testing.assert_array_equal(laid[:, h, lanes], np.asarray(q[:, h]))
        assert not np.delete(laid[:, h], np.r_[lanes], axis=-1).any()
    np.testing.assert_array_equal(np.asarray(unfold_outputs(jnp.asarray(laid), g, n_rep)), np.asarray(q))


# ---------------------------------------------------------------------------
# the two paged kernels at head_dim 64, through their dispatchers
# ---------------------------------------------------------------------------


def _served_pool(H, D, ctx, MB, rng, dtype=jnp.float32):
    """A pool that holds rows' own tokens ``(B, MB * BS, H, D)`` (zeros past a
    row's context), written through the pool's own writer; the rows' blocks a
    permutation. Returns ``(k_tok, v_tok, k_pool, v_pool, table)``."""
    B = len(ctx)
    cache = init_block_cache(L, B * MB, BS, H, D, dtype=dtype)
    table = np.zeros((B, MB), np.int32)
    pages = iter(rng.permutation(np.arange(1, B * MB + 1)))
    for b, n in enumerate(ctx):
        table[b, : -(-n // BS)] = [next(pages) for _ in range(-(-n // BS))]
    pos = np.arange(MB * BS)
    live = pos[None, :] < np.asarray(ctx)[:, None]
    slots = np.where(live, table[:, pos // BS] * BS + pos % BS, -1)
    k_tok, v_tok = (
        jnp.asarray(rng.normal(size=(B, MB * BS, H, D)) * 0.3 * live[:, :, None, None], dtype)
        for _ in range(2)
    )
    k_pool, v_pool = cache.k, cache.v
    for layer in range(L):
        k_pool, v_pool = update_block_cache_at_layer(
            k_pool, v_pool, k_tok, v_tok, jnp.int32(layer), jnp.asarray(slots, jnp.int32)
        )
    return k_tok, v_tok, k_pool, v_pool, jnp.asarray(table)


DECODE_CASES = {
    "k1": dict(),
    "k1_writes": dict(writes=True),
    "k1_writes_bf16": dict(writes=True, dtype="bfloat16"),
    "sink": dict(sink=True),
    "window": dict(window=70),
    "k4_sink": dict(K=4, sink=True),
    "no_live_block": dict(ctx=[0, 150, 0, 33]),
    "no_live_block_writes": dict(ctx=[0, 150, 0, 33], writes=True),
    "2kv": dict(H=2, HQ=4),
}


@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_paged_decode_at_head_dim_64_attends_its_own_head(case):
    """``dispatch_paged_tkg_decode`` over a pool of two heads a row against
    the native masked softmax over the rows' own tokens, at the tolerance the
    head_dim-128 cases hold; with ``new_kv`` the kernel places the token too,
    and the pool afterwards is the per-head write's, bit for bit. A row with
    no live block reads zeros."""
    c = dict(K=1, H=8, HQ=16, ctx=[5 * BS - 3, 40, 2 * BS, 200], writes=False, sink=False,
             window=None, dtype="float32")
    c.update(DECODE_CASES[case])
    K, H, HQ, ctx, D, MB = c["K"], c["H"], c["HQ"], c["ctx"], 64, 8
    tol = 2e-2 if c["dtype"] == "bfloat16" else 2e-5
    rng = np.random.RandomState(len(case))
    B = len(ctx)
    k_tok, v_tok, k_pool, v_pool, table = _served_pool(H, D, ctx, MB, rng, jnp.dtype(c["dtype"]))
    assert k_pool.shape[2:] == (H // 2, BS, 128)
    q = jnp.asarray(rng.randn(B, K, HQ, D) * 0.3, jnp.float32)
    pos = np.stack([np.arange(n - K, n) for n in ctx])  # (B, K): the last K tokens are the pass's
    cols = np.arange(MB * BS)[None, None, :]
    mask = cols <= pos[:, :, None]
    if c["window"]:
        mask &= cols > pos[:, :, None] - c["window"]
    mask = jnp.asarray(mask)[:, None]
    sink = jnp.asarray(rng.randn(HQ) * 0.3, jnp.float32) if c["sink"] else None
    spec = AttnSpec(num_heads=HQ, num_kv_heads=H, head_dim=D, has_sink=c["sink"])
    ref = attention_decode(
        q, k_tok.astype(jnp.float32), v_tok.astype(jnp.float32), mask, spec, sink=sink
    )
    kw = dict(scale=spec.softmax_scale, interpret=True)
    li = jnp.int32(1)
    if c["writes"]:
        # the pass's token is NOT in the pool yet: take it back out, hand it over
        last = np.maximum(np.asarray(ctx) - 1, 0)
        slots = np.where(np.asarray(ctx) > 0, np.asarray(table)[np.arange(B), last // BS] * BS + last % BS, -1)
        slots = jnp.asarray(slots[:, None], jnp.int32)
        new = tuple(t[np.arange(B), last][:, None] for t in (k_tok, v_tok))  # (B, 1, H, D)
        zeros = jnp.zeros_like(new[0])
        k_before, v_before = update_block_cache_at_layer(k_pool, v_pool, zeros, zeros, li, slots)
        out, k_got, v_got = dispatch_paged_tkg_decode(
            q, k_before, v_before, li, table, mask, sink, (*new, slots), **kw
        )
        for got, want in ((k_got, k_pool), (v_got, v_pool)):
            np.testing.assert_array_equal(
                np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32))
            )
    else:
        out = dispatch_paged_tkg_decode(q, k_pool, v_pool, li, table, mask, sink, **kw)
    assert out.shape == q.shape
    live = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live], atol=tol, rtol=tol)
    assert not np.asarray(out)[~live].any()


PREFILL_CASES = {
    # 24 new tokens over 70 of context: the causal frontier inside a group
    "frontier_inside_a_group": dict(prior=[70, 5]),
    "window": dict(prior=[150, 40], window=48),
    "padded_row": dict(prior=[70, 0, 5], new=[24, 0, 24]),
    "2kv_bf16": dict(prior=[70, 5], H=2, HQ=4, dtype="bfloat16"),
}


@pytest.mark.parametrize("case", list(PREFILL_CASES))
def test_paged_prefill_at_head_dim_64_attends_its_own_head(case):
    """``dispatch_paged_flash`` over a pool of two heads a row against the
    native masked softmax over the rows' own tokens (prior context plus causal
    among the chunk's, under ``window`` a lower frontier too)."""
    c = dict(H=8, HQ=16, new=None, window=None, dtype="float32")
    c.update(PREFILL_CASES[case])
    H, HQ, D, MB, Sq = c["H"], c["HQ"], 64, 8, 24
    prior = np.asarray(c["prior"])
    new = np.asarray(c["new"] or [Sq] * len(prior))
    rng = np.random.RandomState(len(case))
    k_tok, v_tok, k_pool, v_pool, table = _served_pool(
        H, D, list(prior + new), MB, rng, jnp.dtype(c["dtype"])
    )
    assert k_pool.shape[2:] == (H // 2, BS, 128)
    B = len(prior)
    q = jnp.asarray(rng.randn(B, Sq, HQ, D) * 0.3, jnp.dtype(c["dtype"]))
    positions = prior[:, None] + np.arange(Sq)[None, :]
    kv_limit = prior + new
    cols = np.arange(MB * BS)[None, None, :]
    mask = (cols <= positions[:, :, None]) & (cols < kv_limit[:, None, None])
    if c["window"]:
        mask &= cols > positions[:, :, None] - c["window"]
    spec = AttnSpec(num_heads=HQ, num_kv_heads=H, head_dim=D)
    ref = attention_decode(
        q.astype(jnp.float32), k_tok.astype(jnp.float32), v_tok.astype(jnp.float32),
        jnp.asarray(mask)[:, None], spec,
    )
    out = dispatch_paged_flash(
        q, k_pool, v_pool, jnp.int32(1), table, jnp.asarray(positions, jnp.int32),
        jnp.asarray(kv_limit, jnp.int32), scale=spec.softmax_scale, n_rep=HQ // H,
        interpret=True, window=c["window"],
    )
    assert out.shape == q.shape and out.dtype == q.dtype
    real = np.arange(Sq)[None, :] < new[:, None]  # a chunk's padded positions attend nothing real
    tol = 2e-2 if c["dtype"] == "bfloat16" else 2e-5
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32))[real], np.asarray(ref)[real], atol=tol, rtol=tol
    )
    assert not np.asarray(out.astype(jnp.float32))[new == 0].any()


@pytest.mark.parametrize("program", ["decode", "decode_writes", "chunk"])
def test_folded_pool_on_a_head_sharded_mesh(program):
    """tp = 2 with 4 KV heads of 64 a shard: two pool rows' heads a device, no
    pair straddling the shards (``kv_streams`` asked inside the mesh). Each
    shard lays its own queries and attends (and writes) its own pool heads;
    output and pool are the unsharded call's, bit for bit, and the pool keeps
    the layout the layer scan carries (``block_cache_spec``)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_inference_tpu.modules.block_kvcache import block_cache_spec
    from neuronx_distributed_inference_tpu.parallel.mesh import MODEL_AXES, build_mesh

    H, HQ, D, MB = 8, 16, 64, 4
    ctx = [100, 0, 37]
    rng = np.random.RandomState(7)
    mesh = build_mesh(tp_degree=2, devices=jax.devices()[:2])
    with jax.set_mesh(mesh):
        assert kv_streams(H, D)[0].heads == 4 and kv_streams(2, D)[0].heads == 2  # 1 head a shard: no fold
    k_tok, v_tok, k_pool, v_pool, table = _served_pool(H, D, ctx, MB, rng)
    B, li = len(ctx), jnp.int32(2)
    spec = AttnSpec(num_heads=HQ, num_kv_heads=H, head_dim=D)
    if program == "chunk":
        q = jnp.asarray(rng.randn(B, 16, HQ, D) * 0.3, jnp.float32)
        pos = jnp.asarray(np.maximum(np.asarray(ctx)[:, None] - 16, 0) + np.arange(16)[None, :], jnp.int32)

        def call(q, k, v):
            return dispatch_paged_flash(
                q, k, v, li, table, pos, jnp.asarray(ctx, jnp.int32),
                scale=spec.softmax_scale, n_rep=HQ // H, interpret=True,
            )
    else:
        q = jnp.asarray(rng.randn(B, 1, HQ, D) * 0.3, jnp.float32)
        mask = jnp.asarray(np.arange(MB * BS)[None, :] < np.asarray(ctx)[:, None])[:, None, None]
        new = None
        if program == "decode_writes":
            last = np.maximum(np.asarray(ctx) - 1, 0)
            slots = np.where(np.asarray(ctx) > 0, np.asarray(table)[np.arange(B), last // BS] * BS + last % BS, -1)
            new = (k_tok[:, :1] + 1.0, v_tok[:, :1] - 1.0, jnp.asarray(slots[:, None], jnp.int32))

        def call(q, k, v):
            return dispatch_paged_tkg_decode(
                q, k, v, li, table, mask, None, new, scale=spec.softmax_scale, interpret=True
            )

    want = jax.jit(call)(q, k_pool, v_pool)
    pool_spec = block_cache_spec().k
    put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))  # noqa: E731
    with jax.set_mesh(mesh):
        got = jax.jit(call)(
            put(q, P(None, None, MODEL_AXES, None)), put(k_pool, pool_spec), put(v_pool, pool_spec)
        )
        hlo = jax.jit(call).lower(
            put(q, P(None, None, MODEL_AXES, None)), put(k_pool, pool_spec), put(v_pool, pool_spec)
        ).compile().as_text()
    for op in ("all-gather", "all-reduce", "all-to-all", "collective-permute", "reduce-scatter"):
        assert op not in hlo, op
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
    if program == "decode_writes":
        for pool in got[1:]:
            assert pool.sharding.is_equivalent_to(NamedSharding(mesh, pool_spec), pool.ndim)
        assert np.asarray(got[1] != k_pool).any()
