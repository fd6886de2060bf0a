"""The traced decoder core.

TPU-native re-design of ``NeuronBaseModel.forward``
(reference: models/model_base.py:86-1653) — the ONE function that is compiled
per (sub-model tag, bucket). Here it is a pure function over pytrees:

    forward(params, cache, inputs, rng) -> StepOutput(tokens, logits?, cache)

specialized by a static :class:`ModelSpec` + phase. Layers run under
``lax.scan`` over stacked layer params (instead of the reference's unrolled
python loop) — one compiled layer body, fast XLA compiles, same math.

Phases (reference sub-model tags, model_wrapper.py:32-37):
- ``context_encoding``: S = context bucket; causal mask; writes KV at
  position_ids; gathers the last valid token's hidden state for the lm head
  (reference model_base.py:1038-1060).
- ``token_generation``: S = 1 (or speculation_length); attends the populated
  cache region sliced to the TKG bucket.

The KV cache is donated by the runner so XLA updates it in place
(reference input/output aliasing, model_wrapper.py:1673-1743).
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from neuronx_distributed_inference_tpu.modules import masks, moe
from neuronx_distributed_inference_tpu.modules.attention import (
    AttnSpec,
    attention_decode,
    attention_prefill,
    o_project,
    qkv_project,
)
from neuronx_distributed_inference_tpu.ops.kernel_mode import kernel_interpret
from neuronx_distributed_inference_tpu.ops.quant import linear as quant_linear
from neuronx_distributed_inference_tpu.modules.kvcache import (
    KVCache,
    QuantizedKV,
    kv_batch_size,
    layer_dequant_factors,
    read_cache_at_layer,
    slot_ids_from_seq_ids,
    update_cache_at_layer,
)
from neuronx_distributed_inference_tpu.modules.norm import apply_norm, rms_norm
from neuronx_distributed_inference_tpu.modules.rope import rope_cos_sin
from neuronx_distributed_inference_tpu.modules.sampling import (
    mask_padded_logits,
    sample_tokens,
)

PHASE_CONTEXT_ENCODING = "context_encoding"
PHASE_TOKEN_GENERATION = "token_generation"
PHASE_SPECULATION = "speculation"
# ragged mixed prefill+decode serving step (models run via mixed_forward; one
# dispatch covers prefill chunks AND decode rows against the paged cache)
PHASE_MIXED = "mixed"


@dataclass(frozen=True)
class LayerGroupSpec:
    """Static description of one contiguous run of structurally-identical
    decoder layers. Heterogeneous stacks (GPT-OSS interleaved sliding/global
    attention, DeepSeek dense-then-MoE) are a sequence of groups; each group
    scans its own stacked params (reference: per-layer module init picks the
    flavor per layer, e.g. modeling_gpt_oss.py sliding layers,
    modeling_deepseek.py first_k_dense_replace)."""

    num_layers: int
    sliding_window: Optional[int] = None
    attention_chunk_size: Optional[int] = None
    # index into the mlp_fn / layer_fn lists the builder provides
    fn_idx: int = 0


@dataclass(frozen=True)
class BlockStepSpec:
    """What a builder says of a model whose decode step fills a BLOCK of
    positions (generation by diffusion over blocks; models/sdar.py). Position
    ``i`` sees ``j`` iff ``j // block_length <= i // block_length``: causal
    between blocks, both ways inside one, in the prompt too. A block's
    unknown positions hold ``mask_token_id``; a denoise pass predicts a token
    and a confidence AT each of them and reveals the ``per_pass`` most
    confident; when none is left a commit pass runs the block once more and
    its K and V stay (runtime/block_step.py has the session's side)."""

    block_length: int
    denoise_steps: int
    mask_token_id: int

    @property
    def per_pass(self) -> int:
        """Positions a denoise pass reveals (all that is left, in a block's last)."""
        return -(-self.block_length // self.denoise_steps)


@dataclass(frozen=True)
class ModelSpec:
    """Static model hyperparams (global, post-GQA-transform head counts)."""

    num_layers: int
    hidden_size: int
    vocab_size: int
    padded_vocab_size: int
    intermediate_size: int
    attn: AttnSpec
    rms_eps: float = 1e-6
    act: str = "silu"
    # attention flavor
    sliding_window: Optional[int] = None
    attention_chunk_size: Optional[int] = None
    # context/sequence parallelism (reference CP/SP, SURVEY §2.9)
    cp_enabled: bool = False
    cp_degree: int = 1
    sequence_parallel: bool = False
    # attention-DP decode: batch-parallel attention over the dp mesh axis
    # (reference attention_base.py:2308-2321)
    attention_dp: int = 1
    # whole-model data parallel over the leading ddp axis (multi-host DCN)
    data_parallel: int = 1
    # sampling
    on_device_sampling: bool = True
    do_sample: bool = False
    max_topk: int = 256
    output_logits: bool = False
    # the step also returns the discrete choices its layers made (an expert
    # layer's selection), as StepOutput.aux: config ``output_choices``
    output_choices: bool = False
    cast_logits_fp32: bool = True
    # rope
    attention_scaling: float = 1.0
    # decoder norm flavor: "rmsnorm" (llama family) or "layernorm" (DBRX)
    norm_type: str = "rmsnorm"
    # ring-buffer KV cache bounded to the sliding window (cache holds W slots;
    # reference kv_cache_manager.py:194-198 bounds the cache to window size)
    bounded_window: Optional[int] = None
    # interleaved per-layer cache sizing (GPT-OSS): sliding layers ring-bound
    # to this window while global layers keep full-length lines; the cache is
    # an InterleavedKVCache (reference gpt_oss_kv_cache_manager.py)
    ring_window: Optional[int] = None
    # heterogeneous layer stacks: None = one uniform group (spec-level
    # sliding_window / attention_chunk_size apply)
    layer_groups: Optional[Tuple[LayerGroupSpec, ...]] = None
    # Granite scalar multipliers (published config keys of the same names):
    # the embedding is scaled, every residual update is scaled, the logits
    # are divided. 1.0 = the plain decoder; nothing is emitted for it.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # a decode step that fills a block of positions (BlockStepSpec); None =
    # one position after another
    block_step: Optional[BlockStepSpec] = None
    # how often the whole stack runs over ONE set of layer weights
    # (models/ouro.py: ``total_ut_steps``). Loop t of layer l keeps its own
    # K/V stream, cache index ``t * L + l``, and the final norm is applied
    # after every loop. 1 = the plain decoder; nothing is emitted for it.
    loop_steps: int = 1


#: the name, in ``StepOutput.aux``, of the experts a pass's expert layers
#: chose: int (B, S, L_moe, k)
EXPERT_CHOICES = "experts"


class LayerStack:
    """A builder's own runner of the WHOLE layer stack, returned by
    ``builder.layer_fn()`` in place of a per-layer function when the stack
    mixes layers that keep different kinds of state (models/granite_hybrid.py:
    paged K/V beside a constant-size recurrent state). :func:`run_decoder_layers`
    hands it the embedded hidden state and the cache pytree and applies the
    final norm to what it returns:

        stack(params, hidden, cache, inputs, *, spec, phase, mlp_fn) -> (hidden, new_cache)

    A stack whose layers make discrete choices (models/zaya.py: an expert
    layer's selection) returns them as a third value under
    ``spec.output_choices``: a dict ``name -> int array (B, S, ...)``, which
    :func:`forward` hands on as ``StepOutput.aux``.
    """

    def __call__(self, params, hidden, cache, inputs, *, spec, phase, mlp_fn):
        raise NotImplementedError


def real_positions(inputs: "StepInputs") -> jax.Array:
    """(B, S) bool: which positions of a pass are real. A position is real
    iff its row is live and it writes K/V somewhere real: a padded chunk tail
    and a row that sits a pass out carry slot_mapping / seq_id -1. The one
    definition: a per-slot state advances over these (:func:`slot_state_rows`)
    and a chunk pass routes these to its experts (:func:`expert_positions`).
    The head of a chunk pass reads a live row's LAST of them, by the slot
    mapping alone (:func:`model_logits`: what a row that sits out gives there
    the host never reads)."""
    valid = jnp.broadcast_to((inputs.seq_ids >= 0)[:, None], inputs.position_ids.shape)
    if inputs.slot_mapping is not None:
        valid = valid & (inputs.slot_mapping >= 0)
    return valid


def expert_positions(inputs: "StepInputs", phase: str) -> Optional[jax.Array]:
    """What a pass hands its expert layers as ``valid``
    (modules/moe.moe_layer): in a PAGED CHUNK pass (:func:`is_paged_chunk`)
    its :func:`real_positions`, so that a padded position is routed to no
    expert: its rows sort last, are in no group and cost the grouped products
    no visit (its output nobody reads: it has no K/V slot and no head). Every
    other pass hands None and routes every position: decode, a block step and
    a speculation verify have no padded position in a live row (and the
    ``dense`` strategy they take has no sort to leave a row out of), context
    encoding and the ragged step are left as they were."""
    if not is_paged_chunk(phase, inputs.slot_mapping, inputs.block_table):
        return None
    return real_positions(inputs)


def slot_state_rows(inputs: "StepInputs", num_slots: int):
    """For a :class:`LayerStack` whose layers keep a constant-size state per
    serving slot, from a pass's inputs: ``valid`` (B, S) — the pass's
    :func:`real_positions`: a position advances the state iff it is real;
    ``reset`` (B,) — state lifetime without a host call: a row
    whose first position in this pass is 0 starts from zero state (a new
    request in a reused slot, a re-prefill after preemption, a probe's fresh
    cache); ``slots`` (B,) — whose state a row advances. The chunk program
    (handed a slot mapping) is chunk_rows wide and its rows carry their slot
    in ``seq_ids``; an empty row gets an index of its own past the last
    slot, so its write-back is dropped and the indices stay unique. The
    decode program has one row per slot: row r owns slot r, ``slots`` None."""
    positions = inputs.position_ids
    valid = real_positions(inputs)
    reset = valid[:, 0] & (positions[:, 0] == 0)
    slots = None
    if inputs.slot_mapping is not None:
        rows = jnp.arange(positions.shape[0], dtype=jnp.int32)
        slots = jnp.where(inputs.seq_ids >= 0, inputs.seq_ids, num_slots + rows)
    return valid, reset, slots


def residual_add(residual: jax.Array, update: jax.Array, spec: "ModelSpec") -> jax.Array:
    """``residual + residual_multiplier * update``. The product is taken in
    float32 and rounded to the model dtype before the add, as the published
    bf16 model takes it (a tensor times a Python scalar): the multiplier
    itself is NOT rounded to the model dtype — 0.22 in bf16 is 0.21973, a
    0.12% error on every residual update, all of one sign (read on the chip,
    PR 28: 19% over the bf16 twin's noise)."""
    if spec.residual_multiplier != 1.0:
        update = (update.astype(jnp.float32) * spec.residual_multiplier).astype(update.dtype)
    return residual + update


@jax.tree_util.register_dataclass
@dataclass
class StepInputs:
    """Per-step device inputs (reference forward args, model_base.py:3373;
    the block-KV fields mirror the vLLM kwargs the reference accepts,
    model_base.py:3392-3396)."""

    input_ids: jax.Array  # (B, S) int32
    attention_mask: jax.Array  # CTE: (B, S); TKG: (B, S_bucket) cache-valid mask
    position_ids: jax.Array  # (B, S) int32
    seq_ids: jax.Array  # (B,) int32 cache-line ids (invalid -> garbage)
    sampling_params: jax.Array  # (B, 3) float32
    slot_mapping: Optional[jax.Array] = None  # (B, S) block-KV flat slots
    block_table: Optional[jax.Array] = None  # (B, MB) block-KV block ids
    adapter_ids: Optional[jax.Array] = None  # (B,) LoRA adapter per request
    # precomputed input embeddings (multimodal prefill: text embeds with
    # image features merged at placeholder positions; reference ImageToText
    # inputs_embeds path) — input_ids still carries shapes/placeholders
    inputs_embeds: Optional[jax.Array] = None  # (B, S, H)
    # token-tree speculation (reference eagle/token_tree.py): cache WRITE
    # slots diverge from RoPE positions (tree nodes occupy distinct slots at
    # the same depth). When set, rope uses these and position_ids carries the
    # write slots (reference rotary_position_ids, modeling_llama.py:1196).
    rope_position_ids: Optional[jax.Array] = None  # (B, S)
    # fully-custom attention mask (B, 1, S, bucket) — tree ancestry masks
    # bypass the standard causal/window mask dispatch
    mask_override: Optional[jax.Array] = None


@jax.tree_util.register_dataclass
@dataclass
class StepOutput:
    tokens: jax.Array  # (B, K) int32
    logits: Optional[jax.Array]  # (B, K, V) or None
    cache: KVCache
    # spec.output_choices: name -> int (B, S, ...) choices of the pass (an
    # expert layer: (B, S, L_moe, k)); None = no leaf, the program is the same
    aux: Optional[dict] = None
    # a block step (spec.block_step, K = block_length): per position the
    # softmax probability of ``tokens`` (float32), and the ids of the row's
    # NEXT pass, ``where(revealed, tokens, input_ids)`` (block_reveal)
    confidence: Optional[jax.Array] = None  # (B, K) float32
    next_ids: Optional[jax.Array] = None  # (B, K) int32


#: sentinel emitted in place of a sampled/argmax token when the row's logits
#: are non-finite. argmax over an all-NaN row returns an arbitrary-but-valid
#: token id, so without the sentinel the host cannot tell a poisoned row from
#: a healthy one off the token fetch it already performs. -1 is outside every
#: vocab, rides the existing int32 token stream (no extra fetch, no program
#: output added), and the serving session quarantines the row on sight
#: (runtime/serving.py FAILED(non_finite)).
NON_FINITE_TOKEN = -1


def mark_non_finite_tokens(tokens: jax.Array, logits: jax.Array) -> jax.Array:
    """Fold a per-position logits-finiteness flag into the token stream:
    positions whose logits contain NaN/Inf emit :data:`NON_FINITE_TOKEN`
    instead of the (meaningless) sampled token. Healthy rows are untouched,
    so byte-identical-output pins across dispatch modes are unaffected."""
    finite = jnp.all(jnp.isfinite(logits), axis=-1)
    return jnp.where(finite, tokens, jnp.int32(NON_FINITE_TOKEN))


@jax.tree_util.register_dataclass
@dataclass
class MixedStepInputs:
    """Device inputs of ONE ragged mixed prefill+decode step (mixed_forward).

    All rows' query tokens are PACKED along one axis of length T (the
    total-query-token bucket): row r owns packed slots
    ``[row_start[r], row_start[r] + row_len[r])``; slots between segments
    are padding (position/slot ``-1``). Row index == serving slot ==
    block-table row, so R is the session's slot count."""

    input_ids: jax.Array  # (1, T) int32 packed tokens
    position_ids: jax.Array  # (1, T) int32 absolute positions; -1 = padded
    slot_mapping: jax.Array  # (1, T) int32 flat paged write slots; -1 = drop
    block_table: jax.Array  # (R, MB) int32
    row_start: jax.Array  # (R,) int32 packed offset per row
    row_len: jax.Array  # (R,) int32 query tokens per row; 0 = inactive
    ctx_len: jax.Array  # (R,) int32 total kv length per row (incl. new)
    sampling_params: jax.Array  # (R, 3) float32
    # async 1-ahead chaining (async_mode): chain_src[t] names the
    # row whose PREVIOUS-step token supplies packed position t's input id
    # (-1 = take input_ids[t] as written by the host). chain_tokens is the
    # previous mixed step's (R, 1) token output — still on device in steady
    # state, so a chained decode row's input never round-trips the host.
    # The synchronous path passes inert values (all -1 / zeros): both modes
    # run ONE program identity, which is what keeps the sealed-retrace and
    # byte-identity pins mode-independent. None (e.g. hand-built audit
    # inputs) skips the gather entirely.
    chain_src: Optional[jax.Array] = None  # (1, T) int32; -1 = host id
    chain_tokens: Optional[jax.Array] = None  # (R, 1) int32


def act_fn(name: str) -> Callable:
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "gelu_pytorch_tanh": partial(jax.nn.gelu, approximate=True),
        "relu": jax.nn.relu,
        # relu(x)^2 (the published nemotron_h ``relu2``)
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


def gated_mlp(params: dict, hidden: jax.Array, spec: ModelSpec) -> jax.Array:
    """SwiGLU MLP (reference NeuronLlamaMLP, modeling_llama.py:338-971)."""
    from neuronx_distributed_inference_tpu.ops.quant import linear

    act = act_fn(spec.act)
    gate = act(linear(params["gate_proj"], hidden))
    up = linear(params["up_proj"], hidden)
    return linear(params["down_proj"], gate * up)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    k_prior: jax.Array,
    v_prior: jax.Array,
    positions: jax.Array,
    W: int,
    aspec: AttnSpec,
    sink: Optional[jax.Array],
) -> jax.Array:
    """Ring decode/prefill-chunk attention: softmax over [prior ring slots |
    in-flight chunk] with masks derived from absolute positions (reference
    windowed TKG mask over a bounded cache, model_base.py:319-340 +
    kv_cache_manager.py:194-198).

    ``k_prior``/``v_prior`` hold the W ring slots read BEFORE this chunk's
    writes landed; ``positions`` are absolute (sentinel-negative for padded).
    """
    p = positions  # (B, S)
    head = p[:, :1] - 1  # (B, 1) last pre-chunk position
    slots = jnp.arange(W, dtype=p.dtype)[None, :]
    # position stored in ring slot s before this chunk wrote anything
    slot_pos = head - ((head - slots) % W)  # (B, W)
    qp = p[:, None, :, None]  # (B, 1, S, 1)
    prior_ok = (
        (slot_pos[:, None, None, :] >= 0)
        & (slot_pos[:, None, None, :] > qp - W)
        & (qp >= 0)
    )  # (B, 1, S, W)
    kp = p[:, None, None, :]  # in-flight token positions (B, 1, 1, S)
    active_ok = (kp >= 0) & (kp <= qp) & (kp > qp - W)
    ring_mask = jnp.concatenate([prior_ok, active_ok], axis=-1)
    keys = jnp.concatenate([k_prior.astype(k.dtype), k], axis=1)
    vals = jnp.concatenate([v_prior.astype(v.dtype), v], axis=1)
    return attention_decode(q, keys, vals, ring_mask, aspec, sink=sink)


def contiguous_decode_attend(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer_idx: jax.Array,
    mask: jax.Array,
    spec: ModelSpec,
    aspec: AttnSpec,
    sink: Optional[jax.Array] = None,
) -> jax.Array:
    """Token-gen attention over one layer of the stacked contiguous cache:
    TKG Pallas kernel when eligible, else bucket-slice + native softmax
    (with attention-DP batch sharding when active). Shared by decoder_layer
    and the EAGLE3 draft layer."""
    from neuronx_distributed_inference_tpu.ops.decode_attention import (
        dispatch_tkg_decode,
        use_tkg_kernel,
    )

    B = q.shape[0]
    bucket = mask.shape[-1]
    plain_parallel = (
        spec.attention_dp == 1 and spec.data_parallel == 1 and not spec.cp_enabled
    )
    if (
        plain_parallel
        and k_cache.shape == v_cache.shape
        and use_tkg_kernel(aspec, q.shape[1], bucket)
    ):
        # decode/speculation attention straight off the stacked cache —
        # no bucket-slice copy, no repeat_kv broadcast (reference TKG
        # kernel, attention_base.py:1467)
        return dispatch_tkg_decode(
            q, k_cache, v_cache, layer_idx, mask, sink,
            scale=aspec.softmax_scale,
            interpret=kernel_interpret(),
        )
    if spec.attention_dp > 1 or spec.data_parallel > 1:
        # batch-parallel decode attention over (ddp, dp): GSPMD all-to-alls
        # heads<->batch around the attention (reference DP decode,
        # attention_base.py:2308-2321)
        from neuronx_distributed_inference_tpu.parallel import attention_dp as adp

        q = adp.shard_decode_q(q)
    k_r, v_r = read_cache_at_layer(
        k_cache, v_cache, layer_idx, B, bucket,
        dp=spec.attention_dp * spec.data_parallel,
    )
    attn_out = attention_decode(q, k_r, v_r, mask, aspec, sink=sink)
    if spec.attention_dp > 1 or spec.data_parallel > 1:
        attn_out = adp.unshard_attn_out(attn_out)
    return attn_out


def decode_kernel_runs(
    spec: ModelSpec, q_len: int, mask_width: int, table_tokens: int, k_shape, v_shape
) -> bool:
    """Whether :func:`paged_attend` hands a pass ``q_len`` wide, which the
    paged prefill kernel does not take, to the paged decode kernel: no batch
    sharded around the attention, a mask as wide as the block table
    (``table_tokens``), a K/V pool of two equal streams, and the kernel's own
    gate (ops/kernel_mode.use_tkg). Static: the serving session asks it too
    (block_kvcache.write_form)."""
    from neuronx_distributed_inference_tpu.ops.decode_attention import use_tkg_kernel

    return (
        spec.attention_dp * spec.data_parallel == 1
        and mask_width == table_tokens
        and tuple(k_shape) == tuple(v_shape)
        and use_tkg_kernel(spec.attn, q_len, mask_width)
    )


def paged_write_attend(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Sq, Hkv, D): this pass's K and V, not yet in the pool
    v: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer_idx: jax.Array,
    mask: jax.Array,
    block_inputs: Tuple[jax.Array, jax.Array, jax.Array],  # paged_block_inputs
    positions: jax.Array,
    spec: ModelSpec,
    sink: Optional[jax.Array] = None,
    window: Optional[int] = None,
    kind: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The paged KV write of a pass and its attention over the pool, for any
    layer that pages K/V at ``(H_kv, D)``: ``(attn_out, k_cache, v_cache)``.
    Write-then-attend (``layer.kv_write``, then :func:`paged_attend` under
    ``layer.attn``), or, where ``block_kvcache.write_form`` says ``kernel``
    (a one-token decode pass that rides the paged decode kernel), ONE call
    under ``layer.attn`` in which the kernel places the token in the block it
    holds for the row anyway and attends as write-then-attend does. Called
    under no scope of the caller's (:func:`decoder_layer`, models/zaya.py).

    ``window``: the layer's OWN window, static (None: it attends its whole
    causal context); ``mask`` already holds it, and the paged prefill kernel
    takes it as a lower frontier (:func:`paged_attend`). ``kind``: a stack
    that mixes kinds of attention layer names each call's kind
    (telemetry/device_scopes.ATTN_KINDS), a scope inside ``layer.attn``."""
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        batch_is_sharded,
        update_block_cache_at_layer,
        write_form,
    )
    from neuronx_distributed_inference_tpu.parallel.sharding import head_shard_degree

    slot_mapping, block_table, kv_limit = block_inputs
    Sq = q.shape[1]
    # asked with the POOL ROW's width and head count: a pool of head_dim 64
    # holds two heads a 128-lane row (block_kvcache.kv_streams) and is, to
    # every writer, a head_dim-128 pool of half the heads
    _, _, pool_heads, bs, pool_width = k_cache.shape
    form = write_form(
        Sq, pool_width, pool_heads // head_shard_degree(),
        quantised=isinstance(k_cache, QuantizedKV), batch_sharded=batch_is_sharded(),
        kernel_runs=decode_kernel_runs(
            spec, Sq, mask.shape[-1], block_table.shape[1] * bs, k_cache.shape, v_cache.shape
        ),
    )
    if form == "kernel":
        from neuronx_distributed_inference_tpu.ops.decode_attention import (
            dispatch_paged_tkg_decode,
        )

        with _attn_scope(kind):
            return dispatch_paged_tkg_decode(
                q, k_cache, v_cache, layer_idx, block_table, mask, sink,
                (k, v, slot_mapping),
                scale=spec.attn.softmax_scale, interpret=kernel_interpret(),
            )
    with jax.named_scope("layer.kv_write"):
        k_cache, v_cache = update_block_cache_at_layer(
            k_cache, v_cache, k, v, layer_idx, slot_mapping
        )
    with _attn_scope(kind):
        attn_out = paged_attend(
            q, k_cache, v_cache, layer_idx, mask, block_table, kv_limit, positions, spec, sink,
            window=window,
        )
    return attn_out, k_cache, v_cache


@contextmanager
def _attn_scope(kind: Optional[str]):
    """``layer.attn``, and inside it the scope of one KIND of attention layer
    where a stack names it (``layer.attn.window`` / ``layer.attn.full``:
    telemetry/device_scopes.ATTN_KINDS). None: ``layer.attn`` alone, the
    names every other model's ops always had."""
    with jax.named_scope("layer.attn"):
        if kind is None:
            yield
        else:
            with jax.named_scope(f"layer.attn.{kind}"):
                yield


def paged_attend(
    q: jax.Array,  # (B, Sq, Hq, D)
    k_cache: jax.Array,  # the stacked block pool, this pass's K/V already written
    v_cache: jax.Array,
    layer_idx: jax.Array,
    mask: jax.Array,
    block_table: jax.Array,  # (B, MB)
    kv_limit: jax.Array,  # (B,)
    positions: jax.Array,
    spec: ModelSpec,
    sink: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Attention of the split serving step over the paged cache, for any
    layer that pages K/V at ``(H_kv, D)``: a prefill chunk rides the paged
    flash kernel, a decode / speculation step the paged TKG kernel, each
    where its gate admits the call (ops/kernel_mode); else blocks are
    gathered by the table and attended natively. Shared by
    :func:`decoder_layer` and the stacks that compute q, k, v their own way
    (models/zaya.py).

    A layer that attends a WINDOW says so with ``window`` (static, its own):
    ``mask`` holds the window already (:func:`build_mask`), the decode kernel
    reads a row's first and last live block off the mask and walks the
    groups between them, and the prefill kernel takes ``window`` as a lower
    frontier beside ``kv_limit``: neither copies a block group that lies
    wholly behind a row's window."""
    from neuronx_distributed_inference_tpu.modules.block_kvcache import (
        read_block_cache_at_layer,
    )
    from neuronx_distributed_inference_tpu.ops.paged_flash_attention import (
        _use_paged_flash,
        dispatch_paged_flash,
    )

    aspec = spec.attn
    Sq = q.shape[1]
    # the paged kernels launch per head shard of the mesh (no collective
    # inside); attention-DP shards the BATCH around the attention
    # instead, and keeps the native path
    dp_shards = spec.attention_dp * spec.data_parallel
    # the paged prefill kernel's mask is causal + prefix, under a lower
    # frontier where the CALL names its layer's window. It has no chunked
    # attention; and a stack that declares windows in its spec but runs
    # layers that do not say theirs (the prestacked form selects a flavor's
    # MASK in the scan: no static window reaches this call) keeps the
    # native path, which attends by the mask
    groups = spec.layer_groups or ()
    chunked = spec.attention_chunk_size or any(g.attention_chunk_size for g in groups)
    windowed = spec.sliding_window or any(g.sliding_window for g in groups)
    frontier_known = not chunked and (window is not None or not windowed)
    if (
        sink is None
        and frontier_known
        and dp_shards == 1
        and _use_paged_flash(aspec, Sq)
    ):
        # chunked/prefix prefill rides the paged flash kernel: blocks are
        # DMA'd straight from the STACKED cache via the layer index and the
        # block table — no gather materialization, no layer's slice
        # (reference flash_pa_with_schedule.py:157). A quantized cache hands
        # the kernel the code blocks plus this layer's per-head dequant
        # factors — the prior-KV path reads narrow tiles
        ks = vs = None
        if isinstance(k_cache, QuantizedKV):
            ks = layer_dequant_factors(k_cache, layer_idx)
            vs = layer_dequant_factors(v_cache, layer_idx)
            k_arr, v_arr = k_cache.data, v_cache.data
        else:
            k_arr, v_arr = k_cache, v_cache
        if spec.block_step is not None:
            # block-causal is the kernel's own rule (kv <= q position, under
            # kv_limit) with each query's frontier at its block's end
            positions = masks.block_frontier(positions, spec.block_step.block_length)
        attn_out = dispatch_paged_flash(
            q, k_arr, v_arr, layer_idx, block_table, positions, kv_limit,
            scale=aspec.softmax_scale,
            n_rep=aspec.num_heads // aspec.num_kv_heads,
            k_scale=ks, v_scale=vs,
            interpret=kernel_interpret(), window=window,
        )
    else:
        from neuronx_distributed_inference_tpu.ops.decode_attention import (
            dispatch_paged_tkg_decode,
        )

        bs = k_cache.shape[3]  # (L, NB+1, Hkv, bs, D) head-major
        if decode_kernel_runs(
            spec, Sq, mask.shape[-1], block_table.shape[1] * bs, k_cache.shape, v_cache.shape
        ):
            # decode/speculation off the paged cache: blocks DMA'd via the
            # block table — no gather materialization (reference block TKG
            # mega kernel, attention_base.py:1609)
            attn_out = dispatch_paged_tkg_decode(
                q, k_cache, v_cache, layer_idx, block_table, mask, sink,
                scale=aspec.softmax_scale,
                interpret=kernel_interpret(),
            )
        else:
            if dp_shards > 1:
                # attention-DP over the paged cache: the batch shards over
                # dp around the attention (GSPMD all-to-all heads<->batch)
                # while the block pool stays REPLICATED over dp — any
                # shard reads any block (the contiguous cache dp-shards
                # its batch dim instead; reference attention_base.py:2308)
                from neuronx_distributed_inference_tpu.parallel import (
                    attention_dp as adp,
                )

                q = adp.shard_decode_q(q)
            k_r, v_r = read_block_cache_at_layer(
                k_cache, v_cache, layer_idx, block_table, head_dim=q.shape[-1]
            )
            attn_out = attention_decode(q, k_r, v_r, mask, aspec, sink=sink)
            if dp_shards > 1:
                attn_out = adp.unshard_attn_out(attn_out)
    return attn_out


def _decoder_layer_mlp(layer_params, hidden, spec, mlp_fn):
    """post-attention norm + MLP + residual."""
    residual = hidden
    with jax.named_scope("layer.norm"):
        hidden = apply_norm(
            hidden, layer_params["post_attention_layernorm"]["weight"], spec.rms_eps,
            spec.norm_type,
        )
    with jax.named_scope("layer.mlp"):
        update = mlp_fn(layer_params["mlp"], hidden, spec)
        if "post_attention_layernorm_2" in layer_params:
            update = _post_norm(update, layer_params["post_attention_layernorm_2"], spec)
        return residual_add(residual, update, spec)


def _post_norm(update, norm_params, spec):
    """A norm on a sub-block's OUTPUT, before the residual add (models/ouro.py:
    ``input_layernorm_2`` after attention, ``post_attention_layernorm_2``
    after the MLP). Taken at trace time from the keys of ``layer_params``: a
    layer without them emits nothing."""
    with jax.named_scope("layer.post_norm"):
        return apply_norm(update, norm_params["weight"], spec.rms_eps, spec.norm_type)


def decoder_layer(
    layer_params: dict,
    hidden: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer_idx: jax.Array,
    mask: jax.Array,
    slot_ids: jax.Array,
    positions: jax.Array,
    spec: ModelSpec,
    phase: str,
    mlp_fn: Callable,
    key_valid: Optional[jax.Array] = None,
    # (slot_mapping (B,S), block_table (B,MB), kv_limit (B,)) in block-KV mode
    block_inputs: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    adapter_ids: Optional[jax.Array] = None,
    # static prefill attention flavor (sliding-window / chunked) for the
    # flash kernel; flavor_select = (uniq_flavors, fl) dispatches between
    # flavors IN-SCAN for prestacked heterogeneous stacks (lax.switch)
    window: Optional[int] = None,
    chunk: Optional[int] = None,
    flavor_select: Optional[Tuple] = None,
    # ragged mixed-step descriptors (row_start, row_len, ctx_len), each (R,):
    # attention runs the ragged paged kernel/fallback instead of the
    # per-phase paths (phase == PHASE_MIXED; mask is unused — the kernel
    # derives it from the descriptors)
    ragged_rows: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    # a stack that mixes kinds of attention layer names this one's
    # (paged_write_attend: a scope inside ``layer.attn``)
    attn_kind: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decoder layer (reference NeuronLlamaDecoderLayer, modeling_llama.py:1188).

    ``k_cache``/``v_cache`` are the FULL stacked caches (all layers); this
    layer's slice is updated in place via ``layer_idx`` (see
    kvcache.update_cache_at_layer). Returns (hidden, k_cache, v_cache).
    """
    aspec = spec.attn
    residual = hidden
    with jax.named_scope("layer.norm"):
        hidden = apply_norm(
            hidden, layer_params["input_layernorm"]["weight"], spec.rms_eps, spec.norm_type
        )
    with jax.named_scope("layer.qkv"):
        q, k, v = qkv_project(
            layer_params["self_attn"], hidden, cos, sin, aspec, adapter_ids=adapter_ids
        )

    # write-then-attend: scatter new KV into this layer's cache first
    # (reference updates via kv_mgr.update_cache per layer, model_base.py:1449)
    is_block = block_inputs is not None
    # interleaved per-layer cache: k_cache/v_cache arrive as (full, ring)
    # stacks and layer_idx as (full_idx, ring_idx, is_sliding) — exactly one
    # of the two scatters below lands; the other drops on its out-of-range
    # layer-index sentinel (scatter mode="drop")
    interleaved = isinstance(k_cache, tuple)
    bounded = spec.bounded_window is not None and not is_block and not interleaved
    # a pass of the split serving step over the paged cache: written AND
    # attended by paged_write_attend, under its own scopes
    paged_step = is_block and phase != PHASE_CONTEXT_ENCODING and ragged_rows is None
    if bounded and phase != PHASE_CONTEXT_ENCODING:
        # ring cache: read the PRIOR window state BEFORE this chunk's writes
        # land (prior/active decomposition — reference compute_for_token_gen's
        # prior/active split, attention_base.py:1909; in-chunk writes may
        # overwrite slots earlier in-chunk queries still need)
        W = spec.bounded_window
        k_prior, v_prior = read_cache_at_layer(
            k_cache, v_cache, layer_idx, q.shape[0], W
        )
    with jax.named_scope("layer.kv_write"):
        if interleaved:
            k_full, k_ring = k_cache
            v_full, v_ring = v_cache
            full_i, ring_i, is_sliding = layer_idx
            W = spec.ring_window
            if phase != PHASE_CONTEXT_ENCODING:
                # prior ring window read BEFORE writes (same hazard as `bounded`);
                # for global layers ring_i clamps to a real slice whose values are
                # never used (the lax.cond below takes the full-cache branch)
                k_prior, v_prior = read_cache_at_layer(
                    k_ring, v_ring, ring_i, q.shape[0], W
                )
            ring_pos = jnp.where(positions >= 0, positions % W, W)
            k_full, v_full = update_cache_at_layer(
                k_full, v_full, k, v, full_i, slot_ids, positions
            )
            k_ring, v_ring = update_cache_at_layer(
                k_ring, v_ring, k, v, ring_i, slot_ids, ring_pos
            )
            k_cache, v_cache = (k_full, k_ring), (v_full, v_ring)
        elif is_block:
            if not paged_step:
                # whole-prompt prefill (attended from k, v below) and the
                # ragged mixed step's packed axis
                from neuronx_distributed_inference_tpu.modules.block_kvcache import (
                    update_block_cache_at_layer,
                )

                k_cache, v_cache = update_block_cache_at_layer(
                    k_cache, v_cache, k, v, layer_idx, block_inputs[0],
                    packed=ragged_rows is not None,
                )
        else:
            if bounded:
                # slot = position mod W; sentinel (negative) positions map out of
                # range and are DROPPED (padded prompt tails must not wrap into
                # live ring slots)
                W = spec.bounded_window
                write_positions = jnp.where(positions >= 0, positions % W, W)
            else:
                write_positions = positions
            k_cache, v_cache = update_cache_at_layer(
                k_cache, v_cache, k, v, layer_idx, slot_ids, write_positions,
                dp=spec.attention_dp * spec.data_parallel,
            )

    sink = layer_params["self_attn"].get("sink", {}).get("weight") if aspec.has_sink else None
    if paged_step:
        attn_out, k_cache, v_cache = paged_write_attend(
            q, k, v, k_cache, v_cache, layer_idx, mask, block_inputs, positions, spec, sink,
            window=window, kind=attn_kind,
        )
    else:
        with jax.named_scope("layer.attn"):
            if phase == PHASE_CONTEXT_ENCODING:
                if spec.cp_enabled:
                    # CP prefill: Q keeps its seq stripe; KV constrained replicated so
                    # GSPMD all-gathers it over the cp axis (reference all-gather-KV
                    # CP, attention_base.py:614-627)
                    from neuronx_distributed_inference_tpu.parallel import context_parallel as cpx

                    q = cpx.shard_q(q)
                    k = cpx.gather_kv(k)
                    v = cpx.gather_kv(v)
                if flavor_select is not None:
                    uniq, fl = flavor_select

                    def _mk(wc):
                        w, c = wc
                        return lambda _: attention_prefill(
                            q, k, v, mask, aspec, sink=sink, key_valid=key_valid,
                            window=w, chunk=c,
                        )

                    attn_out = jax.lax.switch(fl, [_mk(wc) for wc in uniq], None)
                else:
                    attn_out = attention_prefill(
                        q, k, v, mask, aspec, sink=sink, key_valid=key_valid,
                        window=window, chunk=chunk,
                    )
                if spec.cp_enabled:
                    attn_out = cpx.shard_attn_out(attn_out)
            elif ragged_rows is not None:
                # ragged mixed step: prefill-chunk AND decode rows in ONE attention
                # launch off the paged cache, masks derived in-kernel from the
                # (row_start, row_len, ctx_len) descriptors (PAPERS.md ragged paged
                # attention); native gather fallback keeps every config on CPU
                from neuronx_distributed_inference_tpu.ops.ragged_paged_attention import (
                    ragged_attention,
                )

                rs, rl, cl = ragged_rows
                attn_out = ragged_attention(
                    q, k_cache, v_cache, layer_idx, block_inputs[1], positions,
                    rs, rl, cl, aspec, interpret=kernel_interpret(),
                )
            elif bounded:
                attn_out = ring_attention(
                    q, k, v, k_prior, v_prior, positions, spec.bounded_window, aspec, sink
                )
            elif interleaved:
                # decode: sliding layers attend [prior ring | chunk]; global layers
                # attend their full-length cache line. lax.cond executes only the
                # taken branch, so sliding layers never pay the full-cache read
                B = q.shape[0]
                bucket = mask.shape[-1]

                def _global_attend(_):
                    k_r, v_r = read_cache_at_layer(k_full, v_full, full_i, B, bucket)
                    return attention_decode(q, k_r, v_r, mask, aspec, sink=sink)

                def _ring_attend(_):
                    return ring_attention(
                        q, k, v, k_prior, v_prior, positions, spec.ring_window, aspec, sink
                    )

                attn_out = jax.lax.cond(is_sliding == 1, _ring_attend, _global_attend, None)
            else:
                attn_out = contiguous_decode_attend(
                    q, k_cache, v_cache, layer_idx, mask, spec, aspec, sink
                )

    if not interleaved:
        from neuronx_distributed_inference_tpu.modules import tensor_taps

        attn_out = tensor_taps.tap("attn_out", attn_out, layer_idx)
    with jax.named_scope("layer.o_proj"):
        hidden = o_project(layer_params["self_attn"], attn_out, aspec, adapter_ids=adapter_ids)
        if "input_layernorm_2" in layer_params:
            hidden = _post_norm(hidden, layer_params["input_layernorm_2"], spec)
        hidden = residual_add(residual, hidden, spec)

    if mlp_fn is not None:  # None: a block that is the attention part alone
        hidden = _decoder_layer_mlp(layer_params, hidden, spec, mlp_fn)
    if spec.cp_enabled and phase == PHASE_CONTEXT_ENCODING:
        from neuronx_distributed_inference_tpu.parallel import context_parallel as cpx

        hidden = cpx.shard_seq(hidden)
    if not interleaved:
        hidden = tensor_taps.tap("layer_out", hidden, layer_idx)
    return hidden, k_cache, v_cache


def zigzag_cp_perm(S: int, cp: int):
    """Causal-load-balancing sequence permutation for CP prefill (reference
    strided-CP Q split, attention_base.py:698-711 + model_base.py:929-937).

    A contiguous S/cp stripe gives rank 0 the cheap top of the causal
    triangle and rank cp-1 the expensive bottom. Split S into 2*cp chunks and
    give rank r chunks (r, 2cp-1-r): every rank then owns an equal share of
    the triangle (the "zigzag" schedule). Returns (perm, inv) index arrays —
    ``x[:, perm]`` reorders so GSPMD's contiguous cp stripes are balanced,
    ``x[:, inv]`` restores natural order.
    """
    import numpy as np

    nch = 2 * cp
    chunk = S // nch
    order = []
    for r in range(cp):
        order += [r, nch - 1 - r]
    perm = np.concatenate([np.arange(c * chunk, (c + 1) * chunk) for c in order])
    inv = np.empty_like(perm)
    inv[perm] = np.arange(S)
    return jnp.asarray(perm), jnp.asarray(inv)


def build_mask(
    inputs: StepInputs,
    spec: ModelSpec,
    phase: str,
    window: Optional[int] = None,
    chunk: Optional[int] = None,
) -> jax.Array:
    """Mask dispatch per attention flavor/phase (reference model_base.py:211-449).

    ``window``/``chunk`` override the spec-level attention flavor (per-layer-
    group masks for heterogeneous stacks)."""
    if inputs.mask_override is not None:
        return inputs.mask_override
    n_active = inputs.input_ids.shape[1]
    if phase == PHASE_CONTEXT_ENCODING:
        if spec.block_step is not None:
            from neuronx_distributed_inference_tpu.config import BlockStepServingError

            raise BlockStepServingError(
                "a block-step model cannot be served with whole-prompt context "
                "encoding: its prompt runs through the chunk program (block-causal)"
            )
        if chunk:
            return masks.chunked_mask(inputs.attention_mask, inputs.position_ids, chunk)
        if window:
            return masks.windowed_mask(inputs.attention_mask, inputs.position_ids, window)
        return masks.causal_mask(inputs.attention_mask)
    # token generation: base cache-validity mask, then attention-flavor bounds
    if spec.block_step is not None and n_active == spec.block_step.block_length:
        # the block step: its positions see each other and everything the
        # cache-valid mask (up to the block's last position) admits
        mask = masks.token_gen_mask(inputs.attention_mask, n_active)
    elif spec.block_step is not None:
        # a prefill chunk of a block-step model: causal between blocks
        mask = masks.block_causal_token_gen_mask(
            inputs.attention_mask, inputs.position_ids, spec.block_step.block_length
        )
    elif n_active > 1:  # speculation: multi-token decode
        mask = masks.spec_token_gen_mask(inputs.attention_mask, inputs.position_ids)
    else:
        mask = masks.token_gen_mask(inputs.attention_mask, n_active)
    cols = jnp.arange(mask.shape[-1])[None, None, None, :]
    pos = inputs.position_ids[:, None, :, None]  # (B, 1, K, 1)
    if window:
        # decode attends only (pos - window, pos] (reference windowed TKG mask,
        # model_base.py:319-340)
        mask = mask & (cols > pos - window)
    if chunk:
        # chunked attention: same-chunk positions only (reference
        # model_base.py:304-318 chunked TKG mask)
        mask = mask & ((cols // chunk) == (pos // chunk))
    return mask


def embed(params: dict, input_ids: jax.Array) -> jax.Array:
    return jnp.take(params["embed_tokens"]["weight"], input_ids, axis=0)


def lm_head(params: dict, hidden: jax.Array, spec: ModelSpec) -> jax.Array:
    # always (H, V): tied models carry a materialized transposed copy of the
    # embedding (builder.py) so no per-step transpose of the vocab matrix.
    # quant.linear handles a quantized head transparently — the bf16 head
    # was 30% of the int8 decode step's device traffic (PERF.md r5)
    logits = quant_linear(params["lm_head"], hidden)
    if spec.cast_logits_fp32:
        logits = logits.astype(jnp.float32)
    if spec.logits_scaling != 1.0:
        logits = logits / jnp.asarray(spec.logits_scaling, logits.dtype)
    return mask_padded_logits(logits, spec.vocab_size)


def exit_gate(params: dict, hidden: jax.Array) -> jax.Array:
    """(B, S): a looped stack's exit gate after one loop's final norm,
    ``sigmoid(hidden w + b)`` (models/ouro.py ``early_exit_gate``, a
    ``Linear(H, 1)``). The serving step takes its logits from the last loop
    for every position (``early_exit_threshold`` 1) and never computes it:
    it is emitted where a tensor tap (``exit_gate``) asks for it."""
    gate = params["early_exit_gate"]
    logit = hidden @ gate["weight"] + gate["bias"]
    return jax.nn.sigmoid(logit[..., 0])


def gather_last_token(hidden: jax.Array, attention_mask: jax.Array) -> jax.Array:
    """(B, S, H) -> (B, 1, H) at the last valid position per row
    (reference last-token gather, model_base.py:1038-1060)."""
    idx = jnp.maximum(jnp.sum(attention_mask.astype(jnp.int32), axis=1) - 1, 0)
    return jnp.take_along_axis(hidden, idx[:, None, None], axis=1)


def is_paged_chunk(phase: str, slot_mapping, block_table) -> bool:
    """Whether a pass with these fields is a PAGED CHUNK pass (chunked and
    prefix prefill). Field presence is the serving paths' convention: context
    encoding a slot mapping only; decode, a block step and a speculation
    verify a block table only; a chunk pass both."""
    return (
        phase != PHASE_CONTEXT_ENCODING
        and slot_mapping is not None
        and block_table is not None
    )


def paged_block_inputs(inputs: StepInputs, block_size: int):
    """(slot_mapping (B,S), block_table (B,MB), kv_limit (B,)) of a step on
    the paged cache."""
    slot_mapping = inputs.slot_mapping
    if slot_mapping is None:
        # in-graph slot-mapping generation from the block table (reference
        # generate_tokengen_slot_mapping) — the host sends tables only
        from neuronx_distributed_inference_tpu.modules.block_kvcache import (
            slot_mapping_from_block_table,
        )

        slot_mapping = slot_mapping_from_block_table(
            inputs.block_table, inputs.position_ids, block_size
        )
    # valid cache length per row, for the paged flash kernel's bounds
    kv_limit = jnp.sum(inputs.attention_mask.astype(jnp.int32), axis=-1)
    return slot_mapping, inputs.block_table, kv_limit


def run_decoder_layers(
    params: dict,
    hidden: jax.Array,
    cache: KVCache,
    inputs: StepInputs,
    *,
    spec: ModelSpec,
    phase: str,
    mlp_fn: Callable = gated_mlp,
    layer_fn: Optional[Callable] = None,
    capture_layers: Optional[Tuple[int, ...]] = None,
):
    """Layer stack + final norm over an already-embedded hidden state.

    Split out so variants that replace the embedding (EAGLE's fc-fused draft
    input, reference model_base.py:1643-1650) reuse the whole decoder.

    Heterogeneous stacks: when ``spec.layer_groups`` is set,
    ``params["layers"]`` is a LIST of per-group stacked param dicts and
    ``mlp_fn`` / ``layer_fn`` may be lists indexed by each group's
    ``fn_idx``. Each group runs its own ``lax.scan`` with its own attention
    flavor (sliding/chunked/global); the cache and hidden state thread
    through in layer order.

    ``capture_layers``: EAGLE3 multi-layer hidden capture (reference
    model_base.py:1444-1447) — returns a third value, the (B, S, C*H) concat
    of the named layers' outputs, accumulated in-scan (uniform stacks only).

    A looped stack (``spec.loop_steps`` = T > 1, models/ouro.py): the groups'
    scans run inside an outer ``lax.scan`` over the loops with the same
    weights at every loop; loop t of layer l writes and attends cache stream
    ``t * L + l`` (the cache has T x L entries: ``builder.cache_layers()``),
    and the final norm (scope ``loop.norm``) is applied to the carry after
    every loop.
    """
    if isinstance(layer_fn, LayerStack):
        from neuronx_distributed_inference_tpu.modules import tensor_taps

        hidden, new_cache, *aux = layer_fn(
            params, hidden, cache, inputs, spec=spec, phase=phase, mlp_fn=mlp_fn
        )
        hidden = apply_norm(hidden, params["norm"]["weight"], spec.rms_eps, spec.norm_type)
        return (tensor_taps.tap("final_hidden", hidden), new_cache, *aux)

    inv_freq = params["rope"]["inv_freq"]
    rope_pos = (
        inputs.rope_position_ids
        if inputs.rope_position_ids is not None
        else inputs.position_ids
    )
    cos, sin = rope_cos_sin(rope_pos, inv_freq, spec.attention_scaling)

    # three layouts for params["layers"]:
    # - dict, no layer_groups: one uniform stacked scan (the common case)
    # - dict + layer_groups: PRESTACKED heterogeneous flavors (GPT-OSS) —
    #   structurally uniform layers stacked ONCE at load, per-layer flavor
    #   selected in-scan (never concatenated inside the traced function)
    # - list + layer_groups: per-group stacks with different structures
    #   (DeepSeek dense-then-MoE) — one scan per group
    prestacked = spec.layer_groups is not None and isinstance(params["layers"], dict)
    if spec.layer_groups is None:
        groups = [params["layers"]]
        group_specs = [
            LayerGroupSpec(
                num_layers=0,  # derived from params below
                sliding_window=spec.sliding_window,
                attention_chunk_size=spec.attention_chunk_size,
            )
        ]
    elif prestacked:
        groups = [params["layers"]]
        group_specs = list(spec.layer_groups)
    else:
        groups = params["layers"]
        group_specs = list(spec.layer_groups)
    mlp_fns = mlp_fn if isinstance(mlp_fn, (list, tuple)) else [mlp_fn]
    layer_fns = layer_fn if isinstance(layer_fn, (list, tuple)) else [layer_fn]

    if spec.data_parallel > 1:
        # whole-model DP: the batch shards over the leading ddp axis (weights
        # replicate over it); one constraint here propagates everywhere
        from neuronx_distributed_inference_tpu.parallel.sharding import constrain
        from jax.sharding import PartitionSpec as _P

        hidden = constrain(hidden, _P(("ddp",), None, None))

    sp_prefill = (spec.cp_enabled or spec.sequence_parallel) and phase == PHASE_CONTEXT_ENCODING
    if sp_prefill:
        # SP: activations sharded along S over the cp axis (reference SP
        # reduce-scatter of embeddings, model_base.py:1524-1575)
        from neuronx_distributed_inference_tpu.parallel import context_parallel as cpx

        hidden = cpx.shard_seq_from_embed(hidden)

    is_block = inputs.slot_mapping is not None or inputs.block_table is not None
    if is_block:
        slot_ids = inputs.seq_ids  # block layout: writes go via slot_mapping
    else:
        shards = spec.attention_dp * spec.data_parallel
        slot_ids = slot_ids_from_seq_ids(
            inputs.seq_ids, kv_batch_size(cache, shards), dp=shards
        )
    positions = inputs.position_ids

    block_inputs = paged_block_inputs(inputs, cache.block_size) if is_block else None

    # strided-CP causal load balancing (reference attention_base.py:698-711):
    # zigzag-permute the sequence so each cp rank's contiguous stripe owns an
    # equal share of the causal triangle. Q/K/V inherit the permuted order
    # (masks permute on both axes below); cache writes use the permuted
    # POSITIONS so KV lands at absolute slots; hidden is unpermuted at the
    # end. Decode is untouched (it reads the cache by position).
    cp_perm = cp_inv = None
    if (
        sp_prefill
        and spec.cp_enabled
        and spec.cp_degree > 1
        and not is_block
        and spec.ring_window is None
        and capture_layers is None
        and hidden.shape[1] % (2 * spec.cp_degree) == 0
    ):
        cp_perm, cp_inv = zigzag_cp_perm(hidden.shape[1], spec.cp_degree)
        hidden = hidden[:, cp_perm]
        cos = cos[:, cp_perm]
        sin = sin[:, cp_perm]
        positions = positions[:, cp_perm]

    def finalize_mask(mask):
        if cp_perm is not None:
            mask = mask[:, :, cp_perm][:, :, :, cp_perm]
        if sp_prefill and spec.cp_enabled:
            from neuronx_distributed_inference_tpu.parallel import context_parallel as cpx

            return cpx.shard_prefill_mask(mask)
        return mask

    def group_key_valid(*_ignored):
        # prefill exposes key validity so the flash kernel can run — ALL
        # flavors (causal/window/chunk masks fuse into the kernel; not under
        # CP: pallas custom calls don't auto-partition — the CP path uses the
        # GSPMD-partitioned native attention)
        if phase == PHASE_CONTEXT_ENCODING and not spec.cp_enabled:
            return inputs.attention_mask
        return None

    interleaved = spec.ring_window is not None
    if interleaved:
        if not prestacked:
            raise ValueError(
                "ring_window (interleaved per-layer cache sizing) requires a "
                "prestacked layer stack"
            )
        k_cache = (cache.k_full, cache.k_ring)
        v_cache = (cache.v_full, cache.v_ring)
    else:
        k_cache, v_cache = cache.k, cache.v

    from neuronx_distributed_inference_tpu.modules import tensor_taps

    taps_ctx = tensor_taps.active()
    per_layer_taps = taps_ctx is not None and any(
        p in tensor_taps.PER_LAYER_POINTS
        for p in (*taps_ctx.capture, *taps_ctx.replacements)
    )
    if per_layer_taps and (prestacked or len(groups) > 1):
        raise NotImplementedError(
            "per-layer tensor taps require a uniform (single-group) stack"
        )

    if prestacked:
        if capture_layers is not None:
            raise NotImplementedError(
                "capture_layers requires a uniform (single-group) stack"
            )
        # ONE scan over the load-time-stacked params; each layer selects its
        # flavor's mask in-scan. Alternating stacks (GPT-OSS sliding/global)
        # stay depth-independent in program size with no in-graph weight
        # concatenation.
        flavors = [(g.sliding_window, g.attention_chunk_size) for g in group_specs]
        uniq = list(dict.fromkeys(flavors))
        if len(uniq) > 2:
            raise NotImplementedError(
                "prestacked heterogeneous stacks support at most 2 attention "
                "flavors; use per-group param lists instead"
            )
        if len({g.fn_idx for g in group_specs}) != 1:
            raise ValueError("prestacked layer groups must share fn_idx")
        g_mlp = mlp_fns[group_specs[0].fn_idx if len(mlp_fns) > 1 else 0]
        g_layer = layer_fns[group_specs[0].fn_idx if len(layer_fns) > 1 else 0] or decoder_layer
        flavor_masks = [
            finalize_mask(build_mask(inputs, spec, phase, window=w, chunk=c))
            for (w, c) in uniq
        ]
        key_valid = group_key_valid()
        flavor_ids = []
        for f, g in zip(flavors, group_specs):
            flavor_ids.extend([uniq.index(f)] * g.num_layers)
        total = jax.tree.leaves(groups[0])[0].shape[0]
        if total != len(flavor_ids):
            raise ValueError(
                f"layer_groups mismatch: spec says {len(flavor_ids)} layers, "
                f"params carry {total}"
            )
        flavor_arr = jnp.asarray(flavor_ids, jnp.int32)

        if interleaved:
            # per-layer indices into the two stacks; a layer's index into the
            # OTHER flavor's stack is the out-of-range sentinel, which drops
            # that stack's scatter (kvcache.update_cache_at_layer mode="drop")
            if len(uniq) != 2 or (None, None) not in uniq or any(c for (_, c) in uniq):
                raise ValueError(
                    "ring_window needs exactly one sliding and one global "
                    "flavor (no chunked-attention flavors)"
                )
            slide, full_idx, ring_idx = [], [], []
            for g in group_specs:
                s = 1 if g.sliding_window is not None else 0
                slide.extend([s] * g.num_layers)
            nf = nr = 0
            for s in slide:
                full_idx.append(-1 if s else nf)
                ring_idx.append(nr if s else -1)
                nf += 0 if s else 1
                nr += 1 if s else 0
            full_arr = jnp.asarray([x if x >= 0 else nf for x in full_idx], jnp.int32)
            ring_arr = jnp.asarray([x if x >= 0 else nr for x in ring_idx], jnp.int32)
            slide_arr = jnp.asarray(slide, jnp.int32)
            global_mask = flavor_masks[uniq.index((None, None))]
            sliding_mask = flavor_masks[1 - uniq.index((None, None))]

            sw = next(w for (w, _) in uniq if w is not None)

            def fused_body(carry, xs):
                h, k_c, v_c = carry
                layer_params, full_i, ring_i, sl = xs
                fs = None
                if phase == PHASE_CONTEXT_ENCODING:
                    # prefill attends the in-flight chunk only: per-flavor mask
                    # (native path) / per-flavor kernel via lax.switch
                    mask = jnp.where(sl == 1, sliding_mask, global_mask)
                    fs = (((None, None), (sw, None)), sl)
                else:
                    # decode: global layers use this mask; sliding layers build
                    # their ring mask from positions inside decoder_layer
                    mask = global_mask
                h, k_c, v_c = g_layer(
                    layer_params, h, cos, sin, k_c, v_c, (full_i, ring_i, sl),
                    mask, slot_ids, positions, spec, phase, g_mlp,
                    key_valid=key_valid, block_inputs=block_inputs,
                    adapter_ids=inputs.adapter_ids, flavor_select=fs,
                )
                return (h, k_c, v_c), None

            (hidden, k_cache, v_cache), _ = jax.lax.scan(
                fused_body,
                (hidden, k_cache, v_cache),
                (groups[0], full_arr, ring_arr, slide_arr),
            )
        else:

            def fused_body(carry, xs):
                h, k_c, v_c = carry
                layer_params, li, fl = xs
                fs = None
                if len(flavor_masks) == 1:
                    mask = flavor_masks[0]
                else:
                    mask = jnp.where(fl == 1, flavor_masks[1], flavor_masks[0])
                    if phase == PHASE_CONTEXT_ENCODING:
                        fs = (tuple(uniq), fl)
                kw = {}
                if fs is not None:
                    kw["flavor_select"] = fs
                elif phase == PHASE_CONTEXT_ENCODING:
                    kw["window"], kw["chunk"] = uniq[0]
                h, k_c, v_c = g_layer(
                    layer_params, h, cos, sin, k_c, v_c, li, mask, slot_ids, positions,
                    spec, phase, g_mlp, key_valid=key_valid, block_inputs=block_inputs,
                    adapter_ids=inputs.adapter_ids, **kw,
                )
                return (h, k_c, v_c), None

            (hidden, k_cache, v_cache), _ = jax.lax.scan(
                fused_body,
                (hidden, k_cache, v_cache),
                (groups[0], jnp.arange(total, dtype=jnp.int32), flavor_arr),
            )
    else:
        captured = None
        choices = []  # per group, the choices its layers' MLPs returned
        expert_valid = expert_positions(inputs, phase)
        if capture_layers is not None:
            # EAGLE3 multi-layer hidden capture rides the scan carry: one
            # (B, S, H) accumulator per tap, where-selected at its layer index
            # (reference model_base.py:1444-1447)
            if len(groups) != 1:
                raise NotImplementedError(
                    "capture_layers requires a uniform (single-group) stack"
                )
            captured = jnp.zeros((len(capture_layers),) + hidden.shape, hidden.dtype)
            cap_idx = jnp.asarray(capture_layers, jnp.int32)

        def layer_pass(hidden, k_cache, v_cache, captured, stream_base=None):
            """Every group's scan, once. ``stream_base``: a looped stack's
            (``spec.loop_steps`` > 1) first cache index of this pass, ``t * L``:
            layer l then writes and attends stream ``t * L + l`` with the
            weights of layer l. None = a layer's stream is its index."""
            offset = 0
            for group_params, gspec in zip(groups, group_specs):
                window = gspec.sliding_window
                chunk = gspec.attention_chunk_size
                g_mlp = mlp_fns[gspec.fn_idx if len(mlp_fns) > 1 else 0]
                g_layer = layer_fns[gspec.fn_idx if len(layer_fns) > 1 else 0] or decoder_layer

                mask = finalize_mask(build_mask(inputs, spec, phase, window=window, chunk=chunk))
                key_valid = group_key_valid(window, chunk)

                num_layers = jax.tree.leaves(group_params)[0].shape[0]
                if spec.layer_groups is not None and gspec.num_layers != num_layers:
                    raise ValueError(
                        f"layer_groups mismatch: spec says {gspec.num_layers} layers, "
                        f"params carry {num_layers}"
                    )
                # an expert layer whose pass takes the grouped-matmul kernel reads
                # its experts from the group's stacks in place: they stay out of
                # the scanned operands (modules/moe.hoist_expert_stacks)
                expert_stacks = None
                if isinstance(g_mlp, moe.ExpertMlp):
                    B, S = hidden.shape[:2]
                    group_params, expert_stacks = moe.hoist_expert_stacks(
                        group_params, g_mlp.spec, S, B * S, hidden.dtype
                    )
                    # a paged chunk pass routes its real positions alone
                    g_mlp = partial(g_mlp, valid=expert_valid)

                def scan_body(carry, xs, g_mlp=g_mlp, g_layer=g_layer, mask=mask,
                              key_valid=key_valid, window=window, chunk=chunk,
                              expert_stacks=expert_stacks, offset=offset):
                    h, k_c, v_c, cap = carry
                    layer_params, li = xs
                    layer_params = moe.place_expert_stacks(layer_params, expert_stacks, li - offset)
                    chose = []
                    if spec.output_choices:
                        # an MLP that chooses (modules/moe.moe_layer: an expert
                        # layer's selection) returns (update, choices) under
                        # spec.output_choices; the choices ride the scan ys
                        def g_mlp(p, x, s, inner=g_mlp):
                            out = inner(p, x, s)
                            if isinstance(out, tuple):
                                out, picked = out
                                chose.append(picked)
                            return out

                    h, k_c, v_c = g_layer(
                        layer_params, h, cos, sin, k_c, v_c,
                        li if stream_base is None else stream_base + li,
                        mask, slot_ids, positions,
                        spec, phase, g_mlp, key_valid=key_valid, block_inputs=block_inputs,
                        adapter_ids=inputs.adapter_ids, window=window, chunk=chunk,
                    )
                    if cap is not None:
                        hit = (cap_idx == li)[:, None, None, None]
                        cap = jnp.where(hit, h[None].astype(cap.dtype), cap)
                    # per-layer tensor-tap captures ride the scan ys (stacked to
                    # (L, ...) — modules/tensor_taps)
                    return (h, k_c, v_c, cap), (
                        tensor_taps.collect_layer_taps(taps_ctx), chose[0] if chose else None
                    )

                # the full cache rides the CARRY (updated in place per layer); only
                # the layer params are scanned xs — no stacked-ys cache rebuild
                (hidden, k_cache, v_cache, captured), (tap_ys, chose_ys) = jax.lax.scan(
                    scan_body,
                    (hidden, k_cache, v_cache, captured),
                    (group_params, offset + jnp.arange(num_layers, dtype=jnp.int32)),
                )
                tensor_taps.merge_layer_taps(taps_ctx, tap_ys)
                if chose_ys is not None:
                    choices.append(chose_ys)  # (layers of the group, B, S, k)
                offset += num_layers
            return hidden, k_cache, v_cache, captured

        if spec.loop_steps == 1:
            hidden, k_cache, v_cache, captured = layer_pass(hidden, k_cache, v_cache, captured)
        else:
            # a looped stack: the layers' scan inside a scan over the loops,
            # the SAME weights at every loop, the cache on the carry of both;
            # the final norm is applied to the carry after every loop and its
            # output starts the next (so the head below applies none)
            if capture_layers is not None or per_layer_taps or spec.output_choices:
                raise NotImplementedError(
                    "a looped stack (loop_steps > 1) runs without capture_layers, "
                    "per-layer tensor taps and output_choices"
                )
            streams_a_loop = sum(jax.tree.leaves(g)[0].shape[0] for g in groups)
            want_gates = taps_ctx is not None and "exit_gate" in taps_ctx.capture

            def loop_body(carry, t):
                h, k_c, v_c = carry
                h, k_c, v_c, _ = layer_pass(h, k_c, v_c, None, t * streams_a_loop)
                with jax.named_scope("loop.norm"):
                    h = apply_norm(h, params["norm"]["weight"], spec.rms_eps, spec.norm_type)
                    gate = exit_gate(params, h) if want_gates else None
                return (h, k_c, v_c), gate

            (hidden, k_cache, v_cache), gates = jax.lax.scan(
                loop_body,
                (hidden, k_cache, v_cache),
                jnp.arange(spec.loop_steps, dtype=jnp.int32),
            )
            if want_gates:
                tensor_taps.tap("exit_gate", gates)  # (loops, B, S)
    if interleaved:
        new_cache = type(cache)(
            k_full=k_cache[0], v_full=v_cache[0], k_ring=k_cache[1], v_ring=v_cache[1]
        )
    else:
        new_cache = type(cache)(k=k_cache, v=v_cache)

    if cp_perm is not None:
        hidden = hidden[:, cp_inv]  # natural order for last-token gather
        from neuronx_distributed_inference_tpu.parallel import context_parallel as cpx

        hidden = cpx.shard_seq(hidden)

    if spec.loop_steps == 1:  # a looped stack's last loop ended in the final norm
        with jax.named_scope("head"):
            hidden = apply_norm(hidden, params["norm"]["weight"], spec.rms_eps, spec.norm_type)
    hidden = tensor_taps.tap("final_hidden", hidden)
    if capture_layers is not None:
        # (C, B, S, H) -> (B, S, C*H) concat in tap order
        C = captured.shape[0]
        cat = jnp.concatenate([captured[i] for i in range(C)], axis=-1)
        return hidden, new_cache, cat
    if not prestacked and choices:
        # (L_moe, B, S, k) -> (B, S, L_moe, k), as a LayerStack returns them
        chose = jnp.transpose(jnp.concatenate(choices, axis=0), (1, 2, 0, 3))
        return hidden, new_cache, {EXPERT_CHOICES: chose.astype(jnp.int32)}
    return hidden, new_cache


def model_logits(
    params: dict,
    cache: KVCache,
    inputs: StepInputs,
    *,
    spec: ModelSpec,
    phase: str,
    mlp_fn: Callable = gated_mlp,
    layer_fn: Optional[Callable] = None,
    return_hidden: bool = False,
    capture_layers: Optional[Tuple[int, ...]] = None,
    return_aux: bool = False,
):
    """Backbone + lm head, no sampling: returns (logits (B, K, V), new cache)
    [, full-sequence hidden states when ``return_hidden``; or, with
    ``return_aux``, what a :class:`LayerStack` returned beside its cache
    (``spec.output_choices``: the layers' choices), None where nothing did,
    and the logits at every position, None where ``logits`` are those].

    The head runs where a token can leave the program. Context encoding:
    each row's last valid position (K = 1). A paged chunk pass
    (:func:`is_paged_chunk`): each row's last fed position (K = 1); under
    ``spec.output_logits`` the head also runs over all S positions, an extra
    output beside the (B, 1, V) the pass's token is taken from. Every other
    token-generation pass (decode, a block step, a speculation verify): all
    its positions (K = S).

    ``capture_layers``: EAGLE3 — with ``return_hidden``, the returned hidden
    is the (B, S, C*H) multi-layer capture concat instead of the final hidden
    (the reference's full_hidden_states, model_base.py:1470-1476).

    The composable core — fused speculation chains several of these in one
    graph (reference NeuronFusedSpecModel, model_base.py:1656).
    """
    from neuronx_distributed_inference_tpu.modules import tensor_taps

    if inputs.inputs_embeds is not None:
        hidden = inputs.inputs_embeds
    else:
        with jax.named_scope("embed"):
            hidden = embed(params, inputs.input_ids)
            if spec.embedding_multiplier != 1.0:
                hidden = (hidden.astype(jnp.float32) * spec.embedding_multiplier).astype(
                    hidden.dtype
                )
    hidden = tensor_taps.tap("embed", hidden)
    aux = ()
    if capture_layers is not None:
        hidden, new_cache, full_hidden = run_decoder_layers(
            params, hidden, cache, inputs, spec=spec, phase=phase, mlp_fn=mlp_fn,
            layer_fn=layer_fn, capture_layers=capture_layers,
        )
    else:
        hidden, new_cache, *aux = run_decoder_layers(
            params, hidden, cache, inputs, spec=spec, phase=phase, mlp_fn=mlp_fn,
            layer_fn=layer_fn,
        )
        full_hidden = hidden

    def head(h):
        return lm_head(params, h, spec)[..., : spec.vocab_size]  # (B, K, V)

    every_position = None
    if phase == PHASE_CONTEXT_ENCODING:
        hidden = gather_last_token(hidden, inputs.attention_mask)
    with jax.named_scope("head"):
        if is_paged_chunk(phase, inputs.slot_mapping, inputs.block_table):
            if spec.output_logits:
                every_position = head(hidden)
            # a row's fed positions are those with a slot, a prefix of the
            # row; one that sits out (no slot) clamps to position 0: garbage
            # the host never reads (the ragged step's ``rows_h``)
            hidden = gather_last_token(hidden, inputs.slot_mapping >= 0)
        # any other TKG pass: all n_active positions produce logits
        logits = head(hidden)
    logits = tensor_taps.tap("logits", logits)
    if return_hidden:
        return logits, new_cache, full_hidden
    if return_aux:
        return logits, new_cache, (aux[0] if aux else None), every_position
    return logits, new_cache


def decode_steps(
    params: dict,
    cache: KVCache,
    last_tokens: jax.Array,  # (B, 1) int32
    positions: jax.Array,  # (B, 1) int32 write position of last_tokens
    seq_ids: jax.Array,  # (B,)
    sampling_params: jax.Array,  # (B, 3)
    rng: Optional[jax.Array],
    *,
    spec: ModelSpec,
    num_steps: int,
    bucket: int,
    mlp_fn: Callable = gated_mlp,
    layer_fn: Optional[Callable] = None,
    adapter_ids: Optional[jax.Array] = None,
):
    """Run ``num_steps`` whole decode iterations in ONE compiled program.

    TPU-native improvement over the reference's per-token host dispatch
    (model_base.py:3656 hot loop + async_execution.py): a ``lax.scan`` over
    steps keeps tokens, positions, masks, and the donated KV cache entirely
    device-resident, so the host pays one dispatch per CHUNK instead of per
    token — this is what async/1-ahead execution approximates on Neuron.

    Returns (tokens (B, num_steps), logits (B, num_steps, V) | None, cache).
    """
    cols = jnp.arange(bucket, dtype=jnp.int32)[None, :]

    def body(carry, step_rng):
        cache, last, pos = carry
        inputs = StepInputs(
            input_ids=last,
            attention_mask=(cols <= pos).astype(jnp.int32),
            position_ids=pos,
            seq_ids=seq_ids,
            sampling_params=sampling_params,
            adapter_ids=adapter_ids,
        )
        logits, cache = model_logits(
            params, cache, inputs, spec=spec, phase=PHASE_TOKEN_GENERATION,
            mlp_fn=mlp_fn, layer_fn=layer_fn,
        )
        if spec.on_device_sampling and spec.do_sample:
            tok = sample_tokens(logits, sampling_params, step_rng, spec.max_topk, True)
        else:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tok = mark_non_finite_tokens(tok, logits)
        out_logits = logits[:, 0] if spec.output_logits else jnp.zeros((), logits.dtype)
        return (cache, tok, pos + 1), (tok[:, 0], out_logits)

    step_rngs = (
        jax.random.split(rng, num_steps) if (rng is not None and spec.do_sample) else
        jnp.zeros((num_steps,), jnp.uint32)
    )
    if not (rng is not None and spec.do_sample):
        step_rngs = None
        (cache, last, pos), (tokens, logits) = jax.lax.scan(
            lambda c, _: body(c, None), (cache, last_tokens, positions), None,
            length=num_steps,
        )
    else:
        (cache, last, pos), (tokens, logits) = jax.lax.scan(
            body, (cache, last_tokens, positions), step_rngs
        )
    tokens = jnp.swapaxes(tokens, 0, 1)  # (B, num_steps)
    out_logits = jnp.swapaxes(logits, 0, 1) if spec.output_logits else None
    return tokens, out_logits, cache


def mixed_forward(
    params: dict,
    cache,  # BlockKVCache (donated by the runner)
    inputs: MixedStepInputs,
    rng: Optional[jax.Array],
    *,
    spec: ModelSpec,
    mlp_fn: Callable = gated_mlp,
    layer_fn: Optional[Callable] = None,
) -> StepOutput:
    """ONE traced program for a ragged mixed prefill+decode serving step.

    The packed-token analogue of :func:`forward`: the batch axis is the
    TOTAL query-token axis (bucketed by total tokens, not per phase), rows
    are described by ``(row_start, row_len, ctx_len)`` descriptors, cache
    writes scatter through the packed ``slot_mapping``, and attention runs
    the ragged paged kernel (ops/ragged_paged_attention.py). Emits ONE
    next-token per row — sampled from each row's LAST packed query position
    (a prefill chunk that completes its prompt emits the first generated
    token; a decode row its next token), so one dispatch replaces the
    CTE/TKG pair the split serving path interleaved on the host.

    Returns StepOutput with tokens (R, 1); inactive rows (row_len == 0)
    carry garbage tokens the host ignores.
    """
    if spec.layer_groups is not None or spec.bounded_window or spec.ring_window:
        raise NotImplementedError(
            "the ragged mixed step supports uniform full-length layer stacks "
            "only (no ring-bounded/interleaved caches or layer groups)"
        )
    if spec.sliding_window or spec.attention_chunk_size or spec.attn.has_sink:
        raise NotImplementedError(
            "the ragged paged kernel implements the plain causal+prefix mask "
            "only (no sliding-window/chunked attention, no sinks)"
        )
    if layer_fn is not None:
        raise NotImplementedError(
            "the ragged mixed step runs the standard decoder_layer only"
        )
    if spec.loop_steps != 1:
        raise NotImplementedError(
            "the ragged mixed step scans the layers once (no looped stack)"
        )
    if spec.cp_enabled or spec.attention_dp > 1 or spec.data_parallel > 1:
        raise NotImplementedError(
            "the ragged mixed step is single-shard-parallel (tp only)"
        )

    from jax.sharding import PartitionSpec as _P

    from neuronx_distributed_inference_tpu.parallel.sharding import constrain

    input_ids = inputs.input_ids
    if inputs.chain_tokens is not None and inputs.chain_src is not None:
        # device-side chained-id gather (async_mode): packed
        # positions whose chain_src names a row take that row's previous-
        # step token straight off the device — the ragged analogue of the
        # split path's `last_override` chain. A previous-step NON_FINITE
        # sentinel (-1) clamps to token 0: the poisoned row computes finite
        # garbage this (speculative) step and is quarantined at consume.
        R = inputs.chain_tokens.shape[0]
        src = inputs.chain_src
        chained = jnp.take(
            jnp.maximum(inputs.chain_tokens[:, 0], 0),
            jnp.clip(src, 0, R - 1),
        )
        input_ids = jnp.where(src >= 0, chained, input_ids)
    hidden = embed(params, input_ids)  # (1, T, H)
    # pin the scan-carried hidden replicated: without the constraint GSPMD
    # shards the packed hidden along H (propagated back from the per-row
    # gather) and re-gathers it before EVERY layer's qkv matmul — an
    # in-loop activation all-gather per layer (GRAPH303 catches this)
    hidden = constrain(hidden, _P(None, None, None))
    inv_freq = params["rope"]["inv_freq"]
    positions = inputs.position_ids
    cos, sin = rope_cos_sin(positions, inv_freq, spec.attention_scaling)

    k_cache, v_cache = cache.k, cache.v
    block_inputs = (inputs.slot_mapping, inputs.block_table, inputs.ctx_len)
    ragged = (inputs.row_start, inputs.row_len, inputs.ctx_len)
    # paged writes route through slot_mapping; slot_ids is unused ballast
    slot_ids = jnp.zeros((1,), jnp.int32)
    num_layers = jax.tree.leaves(params["layers"])[0].shape[0]

    def scan_body(carry, xs):
        h, k_c, v_c = carry
        layer_params, li = xs
        h = constrain(h, _P(None, None, None))
        h, k_c, v_c = decoder_layer(
            layer_params, h, cos, sin, k_c, v_c, li, None, slot_ids,
            positions, spec, PHASE_MIXED, mlp_fn,
            block_inputs=block_inputs, ragged_rows=ragged,
        )
        return (h, k_c, v_c), None

    (hidden, k_cache, v_cache), _ = jax.lax.scan(
        scan_body,
        (hidden, k_cache, v_cache),
        (params["layers"], jnp.arange(num_layers, dtype=jnp.int32)),
    )
    new_cache = type(cache)(k=k_cache, v=v_cache)

    hidden = apply_norm(hidden, params["norm"]["weight"], spec.rms_eps, spec.norm_type)
    T = hidden.shape[1]
    # per-row last-token gather off the packed axis (the ragged analogue
    # of gather_last_token); inactive rows clamp to slot 0 — garbage the
    # host never reads
    last_idx = jnp.clip(inputs.row_start + inputs.row_len - 1, 0, T - 1)
    rows_h = jnp.take(hidden[0], last_idx, axis=0)[:, None, :]  # (R, 1, H)
    logits = lm_head(params, rows_h, spec)[..., : spec.vocab_size]  # (R, 1, V)
    if spec.on_device_sampling:
        tokens = sample_tokens(
            logits,
            inputs.sampling_params,
            rng if spec.do_sample else None,
            spec.max_topk,
            spec.do_sample,
        )
    else:
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    tokens = mark_non_finite_tokens(tokens, logits)
    out_logits = logits if spec.output_logits else None
    return StepOutput(tokens=tokens, logits=out_logits, cache=new_cache)


def block_reveal(logits: jax.Array, input_ids: jax.Array, block: BlockStepSpec):
    """What a block step predicts and reveals, in the graph: (tokens (B, K)
    int32, confidence (B, K) float32, next ids (B, K) int32). A position's
    token is the argmax over the vocabulary WITHOUT the mask token (a pass
    never predicts a mask: the block step is greedy), its confidence that
    token's softmax probability there. Of the positions that hold the mask
    token the ``per_pass`` most confident are revealed, ties by position;
    the row's next pass is fed ``where(revealed, token, id)``. A pass with no
    mask left (a commit pass) reveals nothing and its next ids are its ids."""
    z = logits.astype(jnp.float32)
    z = jnp.where(jnp.arange(z.shape[-1]) == block.mask_token_id, -jnp.inf, z)
    tokens = jnp.argmax(z, axis=-1).astype(jnp.int32)
    confidence = 1.0 / jnp.sum(jnp.exp(z - jnp.max(z, axis=-1, keepdims=True)), axis=-1)
    masked = input_ids == block.mask_token_id
    score = jnp.where(masked, confidence, -1.0)
    at = jnp.arange(score.shape[1])
    # ahead[b, i, j]: position j is revealed before position i
    ahead = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None]) & (at[None, None, :] < at[None, :, None])
    )
    revealed = masked & (jnp.sum(ahead, axis=-1) < block.per_pass)
    return tokens, confidence, jnp.where(revealed, tokens, input_ids).astype(jnp.int32)


def forward(
    params: dict,
    cache: KVCache,
    inputs: StepInputs,
    rng: Optional[jax.Array],
    *,
    spec: ModelSpec,
    phase: str,
    mlp_fn: Callable = gated_mlp,
    layer_fn: Optional[Callable] = None,
) -> StepOutput:
    """The traced step function (reference NeuronBaseModel.forward, model_base.py:732)."""
    logits, new_cache, aux, every_position = model_logits(
        params, cache, inputs, spec=spec, phase=phase, mlp_fn=mlp_fn, layer_fn=layer_fn,
        return_aux=True,
    )
    if spec.output_choices and aux is None:
        raise NotImplementedError(
            "output_choices: this model's layers return no choices (an expert "
            "layer's selection: modules/moe.moe_layer, models/zaya.py)"
        )
    confidence = next_ids = None
    if (
        spec.block_step is not None
        and phase == PHASE_TOKEN_GENERATION
        and inputs.input_ids.shape[1] == spec.block_step.block_length
        and not is_paged_chunk(phase, inputs.slot_mapping, inputs.block_table)
    ):
        with jax.named_scope("reveal"):
            tokens, confidence, next_ids = block_reveal(logits, inputs.input_ids, spec.block_step)
    else:
        with jax.named_scope("sample"):
            if spec.on_device_sampling:
                tokens = sample_tokens(
                    logits,
                    inputs.sampling_params,
                    rng if spec.do_sample else None,
                    spec.max_topk,
                    spec.do_sample,
                )
            else:
                tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    with jax.named_scope("sample"):
        tokens = mark_non_finite_tokens(tokens, logits)
    if next_ids is not None:
        # the session fetches next_ids alone: a row with a non-finite
        # position carries the sentinel there too
        next_ids = jnp.where(jnp.any(tokens < 0, axis=1, keepdims=True), NON_FINITE_TOKEN, next_ids)

    out_logits = None
    if spec.output_logits:
        out_logits = logits if every_position is None else every_position
    return StepOutput(
        tokens=tokens, logits=out_logits, cache=new_cache, aux=aux,
        confidence=confidence, next_ids=next_ids,
    )
