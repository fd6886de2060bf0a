"""Three readings of a model whose latent attention attends the keys a
learned indexer selects (``model_type: "glm_moe_dsa"``: a pool of three
streams a token, an indexer's top-``index_topk`` before MLA), each with its
own count from the configuration's keys: ``latent_roofline.py`` counts every
live latent, which this model does not attend.

The work is counted from the program's ``nxdi_sparse_keys_*`` counters (the
live keys its indexers scored and the keys its attention read after the
selection, over rows, layers and queries; each taken as its increase over
the traced phase per dispatch of the phase, times the dispatches in the
trace, as ``moe_roofline.py`` takes its counters) and divided by the device
time of the SCOPES ``layer.indexer`` + ``layer.select`` + ``layer.attn`` of
the program (``device_scope.py``'s join), so that the work counted is the
same whatever implements the mechanism later.

    {"reader": "sparse_latent_roofline", "kind": "decode_roofline"}

the bytes a decode row MUST move a layer: its live keys x ``index_head_dim``
x 2 B (the indexer reads every live key) + the keys it attends x
(``kv_lora_rank`` + ``qk_rope_head_dim``) x 2 B, at the chip's peak HBM
bandwidth.

    {"reader": "sparse_latent_roofline", "kind": "prefill_roofline"}

the operations a chunk pass MUST do: a query's live keys x ``index_n_heads``
x ``index_head_dim`` x 2 (the indexer's scores) + the keys it attends x heads
x 2 x ((``qk_nope_head_dim`` + ``qk_rope_head_dim``) + ``v_head_dim``) (the
EXPANDED form's count, as ``latent_roofline.py`` counts it), at the chip's
bf16 peak.

    {"reader": "sparse_latent_roofline", "kind": "stream_roofline",
     "decode_module": "^jit_token_generation_model_decode\\("}

the share of the WHOLE decode module's time that the bytes a decode dispatch
must stream would take at the chip's peak bandwidth: the weights outside the
routed experts (attention with its indexer, the dense layers' MLP, the
router, the shared expert, the norms), the held experts hit
(``nxdi_moe_experts_hit_total``), the head's slice, and the rows' indexer
keys and chosen latents (as ``decode_roofline`` counts them): what bounds any
later claim in such a cell, as ``step.loop_stream_roofline`` does for a
looped stack.

A configuration without ``index_topk``, a program without the counters or
the scopes (an older commit), a trace without the module gives None.
"""

from typing import Optional

from .. import trace_reduce
from . import device_scope
from .ssm_roofline import _increase  # a labelled counter's increase over the traced phase

BYTES = 2  # weights and cache are bf16
SCOPES = r"^layer\.(indexer|select|attn)$"
DISPATCHES = {"decode": ("nxdi_steps_total", {"kind": "decode"}),
              "chunk": ("nxdi_prefill_chunk_dispatches_total", {})}


def latent_bytes(attrs: dict) -> float:
    return (attrs["kv_lora_rank"] + attrs["qk_rope_head_dim"]) * BYTES


def index_key_bytes(attrs: dict) -> float:
    return attrs["index_head_dim"] * BYTES


def pair_flops(attrs: dict) -> tuple:
    """(operations of one scored pair, of one attended pair), a layer."""
    scored = 2.0 * attrs["index_n_heads"] * attrs["index_head_dim"]
    attended = 2.0 * attrs["num_attention_heads"] * (
        attrs["qk_nope_head_dim"] + attrs["qk_rope_head_dim"] + attrs["v_head_dim"])
    return scored, attended


def fixed_weight_bytes(attrs: dict) -> float:
    """Weight bytes every decode dispatch streams whatever it routes: each
    layer's attention with its indexer and norms, the dense layers' MLP, the
    expert layers' router and shared expert, the final norm and the head."""
    H, heads = attrs["hidden_size"], attrs["num_attention_heads"]
    r_q, r_kv = attrs["q_lora_rank"], attrs["kv_lora_rank"]
    d_nope, d_rope, d_v = attrs["qk_nope_head_dim"], attrs["qk_rope_head_dim"], attrs["v_head_dim"]
    attn = (H * r_q + r_q + r_q * heads * (d_nope + d_rope) + H * (r_kv + d_rope) + r_kv
            + r_kv * heads * (d_nope + d_v) + heads * d_v * H)
    index = (r_q * attrs["index_n_heads"] * attrs["index_head_dim"]
             + H * attrs["index_head_dim"] + 2 * attrs["index_head_dim"] + H * attrs["index_n_heads"])
    layers = attrs["num_hidden_layers"]
    dense = min(attrs.get("first_k_dense_replace", 0), layers)
    share = attrs.get("expert_share") or {"of": 1}
    router = H * attrs["n_routed_experts"] * int(share["of"]) + attrs["n_routed_experts"] * int(share["of"])
    shared = 3.0 * H * attrs["moe_intermediate_size"] * (attrs.get("n_shared_experts", 0) or 0)
    total = (layers * (attn + index + 2 * H) + dense * 3.0 * H * attrs["intermediate_size"]
             + (layers - dense) * (router + shared) + H + attrs["vocab_size"] * H)
    return total * BYTES


def expert_bytes(attrs: dict) -> float:
    return 3.0 * attrs["hidden_size"] * attrs["moe_intermediate_size"] * BYTES


def _per_dispatch(counters: dict, name: str, program: str) -> Optional[float]:
    grown = _increase(counters, name, {"program": program})
    counter, labels = DISPATCHES[program]
    dispatches = _increase(counters, counter, labels)
    return None if grown is None or not dispatches else grown / dispatches


def _keys(counters: dict, program: str):
    """(keys scored, keys attended) a dispatch of ``program`` over the phase."""
    return (_per_dispatch(counters, "nxdi_sparse_keys_scored_total", program),
            _per_dispatch(counters, "nxdi_sparse_keys_attended_total", program))


def read(params: dict, ctx: dict) -> Optional[float]:
    attrs, counters = ctx.get("attrs") or {}, ctx.get("counters")
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    if "index_topk" not in attrs or not counters or tr is None or peaks is None:
        return None
    kind = params["kind"]
    if kind == "stream_roofline":
        scored, attended = _keys(counters, "decode")
        experts = _per_dispatch(counters, "nxdi_moe_experts_hit_total", "decode")
        n, seconds = trace_reduce.matching(tr["module_sums"], params["decode_module"])
        if scored is None or attended is None or experts is None or n == 0 or seconds <= 0:
            return None
        chips = max(1, tr["chips"])
        needed = (fixed_weight_bytes(attrs) + experts * expert_bytes(attrs)
                  + scored * index_key_bytes(attrs) + attended * latent_bytes(attrs))
        return 100.0 * (needed / peaks["hbm_bytes_per_s"]) / (seconds / n)
    program = {"decode_roofline": "decode", "prefill_roofline": "chunk"}.get(kind)
    if program is None:
        raise ValueError(f"unknown sparse_latent_roofline kind {kind!r}")
    scored, attended = _keys(counters, program)
    scope_ms = device_scope.read(
        {"kind": "scope_ms_per_dispatch", "program": program, "scope": SCOPES}, ctx)
    if scored is None or attended is None or not scope_ms:
        return None
    if program == "decode":
        least_s = (scored * index_key_bytes(attrs) + attended * latent_bytes(attrs)) / peaks["hbm_bytes_per_s"]
    else:
        per_scored, per_attended = pair_flops(attrs)
        least_s = (scored * per_scored + attended * per_attended) / peaks["bf16_flops_per_s"]
    if least_s <= 0:
        return None
    return 100.0 * least_s / (scope_ms * 1e-3)
