"""TPU device-spec registry + the analytic roofline projection model.

One table of nameplate numbers (peak FLOP/s by dtype, HBM GB/s, ICI GB/s)
and one set of closed-form llama-shaped cost formulas, consumed by THREE
places so the repo has a single source of truth for "how fast should this
be":

- :mod:`.cost_audit` projects a lower-bound step time / tok/s for every
  audited (family, bucket) program from its HLO-derived FLOPs/bytes census;
- ``bench.py`` emits ``projected_tok_s`` / ``model_error_frac`` beside every
  measured row (the measured-vs-predicted hook hardware session zero
  validates);
- ``python -m neuronx_distributed_inference_tpu.analysis.device_model``
  prints the markdown projection tables committed in PERF.md — the
  hand-written estimates those tables replace are gone; regenerate, don't
  re-type.

The registry numbers are NAMEPLATE (vendor peak). Measured efficiency on
this stack is ~67–92% of nameplate depending on op mix (PERF.md rounds
2–5); projections here are therefore LOWER BOUNDS on time (upper bounds on
tok/s), which is exactly what a regression gate wants: a measured number
can approach the bound but a model change that moves the bound itself must
be reviewed.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Dict, Optional

# ---------------------------------------------------------------------------
# device registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DeviceSpec:
    """Nameplate per-chip numbers. ``peak_flops`` is keyed by compute dtype
    (matmul operand dtype); fp32 on v5e-class chips runs the bf16x3 path at
    ~1/3 the bf16 rate (PERF.md round 6)."""

    name: str
    peak_flops: Dict[str, float]  # dtype -> FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s per chip (one direction)
    hbm_capacity: int  # bytes
    vmem_bytes: int  # per-core scoped VMEM a single Pallas kernel may hold

    def peak(self, dtype: str) -> float:
        return self.peak_flops.get(_canon_dtype(dtype), self.peak_flops["bfloat16"])

    @property
    def ridge_flops_per_byte(self) -> float:
        """bf16 arithmetic-intensity ridge point: programs above it are
        compute-bound, below it bandwidth-bound (COST504)."""
        return self.peak_flops["bfloat16"] / self.hbm_bw


def _canon_dtype(dtype: str) -> str:
    d = str(dtype).lower()
    if d in ("bf16", "bfloat16"):
        return "bfloat16"
    if d in ("f32", "float32"):
        return "float32"
    if d.startswith("int8") or d.startswith("fp8") or d.startswith("float8"):
        return "int8"
    return d


#: per-chip nameplate specs. v5e matches the numbers every PERF.md roofline
#: already uses (197 TFLOP/s bf16, 819 GB/s HBM); the others are the public
#: vendor peaks — correct them from measurements if a hardware session
#: disagrees (the cost baselines pin FLOPs/bytes, not these constants).
#:
#: ``vmem_bytes`` is the per-core scoped-VMEM budget a single Pallas kernel
#: invocation can hold (operand windows + scratch), i.e. the compiler's
#: scoped-vmem limit (16 MiB class per the Pallas guide; Mosaic's
#: ``vmem_limit_bytes`` default). v6e carries the doubled Trillium on-chip
#: memory. KERN701 budgets against DEFAULT_DEVICE, so the v5e figure is the
#: binding one — keep it conservative and let a hardware session raise it.
DEVICE_REGISTRY: Dict[str, DeviceSpec] = {
    "v5e": DeviceSpec(
        name="v5e",
        peak_flops={"bfloat16": 197e12, "int8": 394e12, "float32": 197e12 / 3},
        hbm_bw=819e9,
        ici_bw=200e9,  # 1600 Gbps
        hbm_capacity=16 * 1024**3,
        vmem_bytes=16 * 1024**2,  # 16 MiB/core scoped VMEM (+128 KiB SMEM)
    ),
    "v5p": DeviceSpec(
        name="v5p",
        peak_flops={"bfloat16": 459e12, "int8": 918e12, "float32": 459e12 / 3},
        hbm_bw=2765e9,
        ici_bw=600e9,  # 4800 Gbps
        hbm_capacity=95 * 1024**3,
        vmem_bytes=16 * 1024**2,  # 16 MiB/core scoped VMEM
    ),
    "v6e": DeviceSpec(
        name="v6e",
        peak_flops={"bfloat16": 918e12, "int8": 1836e12, "float32": 918e12 / 3},
        hbm_bw=1640e9,
        ici_bw=448e9,  # 3584 Gbps
        hbm_capacity=32 * 1024**3,
        vmem_bytes=32 * 1024**2,  # Trillium doubles per-core on-chip memory
    ),
    "v4": DeviceSpec(
        name="v4",
        peak_flops={"bfloat16": 275e12, "int8": 275e12, "float32": 275e12 / 3},
        hbm_bw=1228e9,
        ici_bw=300e9,  # 2400 Gbps
        hbm_capacity=32 * 1024**3,
        vmem_bytes=16 * 1024**2,  # 16 MiB VMEM/core (+128 MiB chip CMEM)
    ),
}

#: the bench's target chip — projections on a host with no resolvable TPU
#: (the CPU harness) are computed against this spec with model_error_frac
#: left null (bench contract, tests/test_bench_smoke.py)
DEFAULT_DEVICE = "v5e"

_KIND_PATTERNS = (
    # substrings of jax's device_kind / str(device), most specific first
    ("v5 lite", "v5e"),
    ("v5e", "v5e"),
    ("v6 lite", "v6e"),
    ("v6e", "v6e"),
    ("v5p", "v5p"),
    ("v5", "v5p"),  # bare "TPU v5" is the p variant; lite matched above
    ("v4", "v4"),
)


def resolve_device(device_kind: str) -> Optional[DeviceSpec]:
    """Map a jax ``device_kind``/``str(device)`` (e.g. ``"TPU v5 lite0"``)
    to a registry spec; None for CPU/unknown devices (the caller then
    projects against :data:`DEFAULT_DEVICE` and reports no model error)."""
    kind = (device_kind or "").lower()
    if "tpu" not in kind and not kind.startswith("v"):
        return None
    for pat, name in _KIND_PATTERNS:
        if pat in kind:
            return DEVICE_REGISTRY[name]
    return None


def get_device(name: str = DEFAULT_DEVICE) -> DeviceSpec:
    return DEVICE_REGISTRY[name]


# ---------------------------------------------------------------------------
# model shapes (bench.py imports these — one definition)
# ---------------------------------------------------------------------------

LLAMA_1B = dict(
    model_type="llama",
    hidden_size=2048,
    intermediate_size=8192,
    num_attention_heads=32,
    num_key_value_heads=8,
    num_hidden_layers=16,
    vocab_size=128256,
    rms_norm_eps=1e-5,
    rope_theta=500000.0,
    max_position_embeddings=2048,
    hidden_act="silu",
    tie_word_embeddings=True,
    head_dim=64,
)

LLAMA_8B = dict(
    model_type="llama",
    hidden_size=4096,
    intermediate_size=14336,
    num_attention_heads=32,
    num_key_value_heads=8,
    num_hidden_layers=32,
    vocab_size=128256,
    rms_norm_eps=1e-5,
    rope_theta=500000.0,
    max_position_embeddings=2048,
    hidden_act="silu",
    tie_word_embeddings=False,
    head_dim=128,
)


def _itemsize(dtype: str) -> float:
    # int4: packed grouped codes (ops/quant_matmul) — 0.5 byte/param plus
    # one f32 scale per 128-group per out channel (4/128 byte/param), folded
    # in so the projection charges what the decode stream actually reads
    return {"bfloat16": 2, "int8": 1, "float32": 4, "int4": 0.5 + 4 / 128}[
        _canon_dtype(dtype)
    ]


def matmul_params(attrs: dict) -> Dict[str, int]:
    """Matmul-weight element counts of a llama-shaped model — the weights a
    decode step must stream from HBM (embedding is a gather, not a stream;
    tied-embedding models materialize a separate (H, V) lm_head at load, so
    lm_head always streams)."""
    H = attrs["hidden_size"]
    I = attrs["intermediate_size"]
    nq = attrs["num_attention_heads"]
    nkv = attrs["num_key_value_heads"]
    D = attrs.get("head_dim") or H // nq
    L = attrs["num_hidden_layers"]
    V = attrs["vocab_size"]
    per_layer = H * nq * D + 2 * H * nkv * D + nq * D * H + 3 * H * I
    return {
        "per_layer": per_layer,
        "layers_total": per_layer * L,
        "lm_head": H * V,
        "total": per_layer * L + H * V,
    }


def kv_bytes_per_token(attrs: dict, kv_dtype: str = "bfloat16") -> float:
    """Cache bytes one token occupies across all layers (K + V), codes only
    — the per-(layer, head) scales of a quantized cache are O(L·H) floats,
    noise next to the code stream."""
    nkv = attrs["num_key_value_heads"]
    D = attrs.get("head_dim") or attrs["hidden_size"] // attrs["num_attention_heads"]
    L = attrs["num_hidden_layers"]
    return 2 * L * nkv * D * _itemsize(kv_dtype)


def decode_projection(
    attrs: dict,
    *,
    batch: int,
    kv_width: int,
    weight_dtype: str = "bfloat16",
    kv_dtype: str = "bfloat16",
    device: Optional[DeviceSpec] = None,
    tp: int = 1,
) -> Dict[str, float]:
    """Lower-bound decode step time / tok/s on one chip (``tp`` > 1 divides
    both streams across chips; ICI cost of the per-layer all-reduce is the
    cost census' job, not this closed form's).

    t_step >= max(weight+KV bytes / HBM bw, matmul+attention FLOPs / peak).
    Decode on every committed shape is HBM-bound; the FLOPs term exists so
    large-batch projections stay honest.
    """
    spec = device or get_device()
    mm = matmul_params(attrs)
    nq = attrs["num_attention_heads"]
    D = attrs.get("head_dim") or attrs["hidden_size"] // nq
    L = attrs["num_hidden_layers"]

    weight_bytes = mm["total"] * _itemsize(weight_dtype)
    kv_read = batch * kv_width * kv_bytes_per_token(attrs, kv_dtype)
    hbm_bytes = (weight_bytes + kv_read) / tp
    # per token: every matmul weight once (2 FLOPs/param) + QK^T and PV at
    # the live kv width (2 + 2 FLOPs per (head, pos, dim) slot)
    flops = batch * (2 * mm["total"] + 4 * L * nq * D * kv_width) / tp

    t_hbm = hbm_bytes / spec.hbm_bw
    t_flops = flops / spec.peak("bfloat16")  # matmuls compute in bf16
    t_step = max(t_hbm, t_flops)
    return {
        "t_step_s": t_step,
        "t_hbm_s": t_hbm,
        "t_flops_s": t_flops,
        "tok_s": batch / t_step,
        "bound": "hbm" if t_hbm >= t_flops else "flops",
        "weight_bytes": int(weight_bytes),
        "kv_read_bytes": int(kv_read),
        "device": spec.name,
    }


def prefill_projection(
    attrs: dict,
    *,
    batch: int,
    seq: int,
    weight_dtype: str = "bfloat16",
    device: Optional[DeviceSpec] = None,
    tp: int = 1,
) -> Dict[str, float]:
    """Lower-bound prefill (context-encoding) pass: matmul FLOPs over S
    tokens + causal attention FLOPs (S²/2), against peak; plus the one
    weight stream against HBM."""
    spec = device or get_device()
    mm = matmul_params(attrs)
    nq = attrs["num_attention_heads"]
    D = attrs.get("head_dim") or attrs["hidden_size"] // nq
    L = attrs["num_hidden_layers"]

    flops = batch * (2 * mm["total"] * seq + 4 * L * nq * D * seq * seq / 2) / tp
    hbm_bytes = mm["total"] * _itemsize(weight_dtype) / tp
    t_flops = flops / spec.peak("bfloat16")
    t_hbm = hbm_bytes / spec.hbm_bw
    t_pass = max(t_flops, t_hbm)
    return {
        "t_pass_s": t_pass,
        "tok_s": batch * seq / t_pass,
        "bound": "flops" if t_flops >= t_hbm else "hbm",
        "flops": int(flops),
        "device": spec.name,
    }


#: the bench spec-serving draft shape: a 1B-width, 4-layer truncation (the
#: EAGLE-class "few-layer draft over the target's width" regime; bench.py's
#: spec-ragged row builds its random-weight draft from the same dict so the
#: projection and the measurement share one shape definition)
LLAMA_1B_DRAFT4 = dict(LLAMA_1B, num_hidden_layers=4)


def expected_accept_tokens(acceptance: float, draft_len: int) -> float:
    """Expected tokens committed per speculation round under greedy
    contiguous-match verification with per-draft acceptance probability
    ``acceptance`` and ``draft_len`` drafted tokens: the leading-match
    length of a geometric chain, 1 + a + a² + … + a^L (PERF.md
    "acceptance-vs-tok/s"). At a = 0.8, L = 3 that is 2.95 tokens/round."""
    a = float(acceptance)
    L = int(draft_len)
    if a >= 1.0:
        return L + 1.0
    return (1.0 - a ** (L + 1)) / (1.0 - a)


def spec_decode_projection(
    attrs: dict,
    *,
    batch: int,
    kv_width: int,
    acceptance: float,
    draft_len: int,
    draft_attrs: Optional[dict] = None,
    weight_dtype: str = "bfloat16",
    kv_dtype: str = "bfloat16",
    device: Optional[DeviceSpec] = None,
    tp: int = 1,
) -> Dict[str, float]:
    """Draft-assisted decode ceiling at a given ACCEPTANCE RATE — the
    acceptance-parameterized projection the spec-serving bench row and
    ``--compare`` consume.

    One round = one packed verify pass over ``draft_len + 1`` query tokens
    per row (HBM cost == a plain decode step: weights stream once, the KV
    read is the same cache walk; FLOPs scale by the extra query tokens —
    still far under the ridge at serving widths) + ``draft_len`` sequential
    draft decode steps on ``draft_attrs`` (default :data:`LLAMA_1B_DRAFT4`).
    Expected committed tokens/round follow the geometric acceptance chain
    (:func:`expected_accept_tokens`), so::

        tok_s = batch * E[tokens/round] / (t_verify + draft_len * t_draft)

    At acceptance 1.0 with a free draft this recovers (draft_len+1)× the
    plain decode ceiling; at acceptance 0 it degrades to plain decode taxed
    by the draft — the model PERF r5's ">500 tok/s at int8+EAGLE
    (acceptance 0.8)" figure comes from."""
    spec = device or get_device()
    verify = decode_projection(
        attrs, batch=batch, kv_width=kv_width, weight_dtype=weight_dtype,
        kv_dtype=kv_dtype, device=spec, tp=tp,
    )
    # the verify pass computes draft_len+1 query positions per row: same
    # HBM traffic, (draft_len+1)x the matmul/attention FLOPs
    t_verify = max(verify["t_hbm_s"], verify["t_flops_s"] * (draft_len + 1))
    d_attrs = draft_attrs if draft_attrs is not None else LLAMA_1B_DRAFT4
    draft_step = decode_projection(
        d_attrs, batch=batch, kv_width=kv_width, weight_dtype=weight_dtype,
        kv_dtype=kv_dtype, device=spec, tp=tp,
    )
    t_round = t_verify + draft_len * draft_step["t_step_s"]
    e_tokens = expected_accept_tokens(acceptance, draft_len)
    return {
        "t_round_s": t_round,
        "t_verify_s": t_verify,
        "t_draft_s": draft_len * draft_step["t_step_s"],
        "expected_tokens_per_round": e_tokens,
        "acceptance": float(acceptance),
        "draft_len": int(draft_len),
        "tok_s": batch * e_tokens / t_round,
        "bound": verify["bound"],
        "weight_bytes": verify["weight_bytes"],
        "kv_read_bytes": verify["kv_read_bytes"],
        "device": spec.name,
    }


# ---------------------------------------------------------------------------
# bench-row projection table (the non-tiny bench.py suite shapes)
# ---------------------------------------------------------------------------

#: each measured bench row's analytic shape — kv_width is the TKG bucket the
#: measured decode actually runs at (bench._suite_params non-tiny values);
#: kind "serving" projects the aggregate device ceiling at the slot count.
BENCH_ROW_MODELS: Dict[str, dict] = {
    "bf16_1b_bs1": dict(model=LLAMA_1B, kind="decode", batch=1, kv_width=512,
                        weight_dtype="bfloat16", kv_dtype="bfloat16"),
    "bf16_1b_bs4": dict(model=LLAMA_1B, kind="decode", batch=4, kv_width=512,
                        weight_dtype="bfloat16", kv_dtype="bfloat16"),
    "int8_1b_bs1": dict(model=LLAMA_1B, kind="decode", batch=1, kv_width=512,
                        weight_dtype="int8", kv_dtype="bfloat16"),
    "serving_1b_int8": dict(model=LLAMA_1B, kind="serving", batch=8,
                            kv_width=1024, weight_dtype="int8",
                            kv_dtype="bfloat16"),
    "serving_1b_int8_ragged": dict(model=LLAMA_1B, kind="serving", batch=8,
                                   kv_width=1024, weight_dtype="int8",
                                   kv_dtype="bfloat16"),
    "serving_1b_int8_ragged_async": dict(model=LLAMA_1B, kind="serving",
                                         batch=8, kv_width=1024,
                                         weight_dtype="int8",
                                         kv_dtype="bfloat16"),
    # spec-serving row (serving_spec_ragged): the acceptance-parameterized
    # projection — PERF r5's committed operating point is acceptance 0.8
    # with a k=4 program (3 drafts); bench.py records the MEASURED
    # acceptance beside it (spec_ragged_acceptance) so hardware session
    # zero can re-project at the observed rate before judging the error
    "serving_1b_int8_spec_ragged": dict(model=LLAMA_1B, kind="serving_spec",
                                        batch=8, kv_width=1024,
                                        weight_dtype="int8",
                                        kv_dtype="bfloat16",
                                        acceptance=0.8, draft_len=3,
                                        draft=LLAMA_1B_DRAFT4),
    # router row, as committed: 2 replicas SHARING one chip, 8-request mix
    # -> each replica streams its own weight copy for its 4-request share,
    # so the aggregate ceiling is the batch-4 single-chip projection (NOT
    # batch-8: two weight streams halve the per-replica bandwidth). On
    # scale-out hardware bench.py multiplies by the count of
    # non-overlapping replica meshes instead.
    "serving_1b_int8_router": dict(model=LLAMA_1B, kind="serving", batch=4,
                                   kv_width=1024, weight_dtype="int8",
                                   kv_dtype="bfloat16"),
    # threaded-stepping row (router_threading): the DEVICE ceiling is the
    # same as the sequential router row — threading removes host
    # serialization, it does not change what each replica's chip streams;
    # the row's win shows up as measured tok/s approaching this same
    # projection (and in router_step_overlap_frac), not as a new ceiling
    "serving_1b_int8_router_threaded": dict(
        model=LLAMA_1B, kind="serving", batch=4, kv_width=1024,
        weight_dtype="int8", kv_dtype="bfloat16"),
    # disaggregated-prefill-tier row (ISSUE 15): the DEVICE ceiling is the
    # router row's — the tier moves WHERE prefill runs (a dedicated
    # replica), not what each decode chip streams per request; the row's
    # own numbers (handoffs, hand-off failure census, local-prefill
    # fallbacks) are containment metrics the device model does not project
    "serving_1b_int8_disagg": dict(model=LLAMA_1B, kind="serving", batch=4,
                                   kv_width=1024, weight_dtype="int8",
                                   kv_dtype="bfloat16"),
    # elastic add/retire row (ISSUE 20): the DEVICE ceiling is the router
    # row's — retiring one replica mid-drain and adding a fresh one changes
    # WHICH replica streams each request, not what a replica's chip streams
    # per step; the row's own numbers (retired/added counts, leaked blocks
    # and threads, attainment vs the static drain) are stewardship metrics
    # the device model does not project
    "serving_1b_int8_elastic": dict(model=LLAMA_1B, kind="serving", batch=4,
                                    kv_width=1024, weight_dtype="int8",
                                    kv_dtype="bfloat16"),
    # open-loop goodput rows (ISSUE 14): the DEVICE ceiling is the same
    # full-slot serving projection — goodput (SLO-met tokens/s) is bounded
    # by throughput, which is bounded by this; the rows' own numbers
    # (attainment, dip, recovery) are workload metrics the device model
    # does not project. The chaos row's 2 replicas share the committed
    # 1-chip harness, so its ceiling stays the single-mesh projection.
    "serving_1b_int8_goodput": dict(model=LLAMA_1B, kind="serving", batch=8,
                                    kv_width=1024, weight_dtype="int8",
                                    kv_dtype="bfloat16"),
    "serving_1b_int8_goodput_burst": dict(model=LLAMA_1B, kind="serving",
                                          batch=8, kv_width=1024,
                                          weight_dtype="int8",
                                          kv_dtype="bfloat16"),
    "serving_1b_int8_goodput_chaos": dict(model=LLAMA_1B, kind="serving",
                                          batch=8, kv_width=1024,
                                          weight_dtype="int8",
                                          kv_dtype="bfloat16"),
    # disaggregated chaos row (ISSUE 15): same full-slot serving ceiling —
    # the prefill-tier kill is a containment scenario (decode capacity
    # survives; placements degrade to local prefill), not a new ceiling
    "serving_1b_int8_disagg_chaos": dict(model=LLAMA_1B, kind="serving",
                                         batch=8, kv_width=1024,
                                         weight_dtype="int8",
                                         kv_dtype="bfloat16"),
    "int8_8b_bs1": dict(model=LLAMA_8B, kind="decode", batch=1, kv_width=512,
                        weight_dtype="int8", kv_dtype="bfloat16"),
    # w4 rows (ISSUE 17): grouped-int4 packed weights (ops/quant_matmul).
    # The 8B decode row is the flagship — weight-read bytes drop ~2x vs the
    # int8 row above, and the projection's ceiling moves with them.
    "bf16_8b_int4": dict(model=LLAMA_8B, kind="decode", batch=1, kv_width=512,
                         weight_dtype="int4", kv_dtype="bfloat16"),
    "serving_1b_int4_ragged": dict(model=LLAMA_1B, kind="serving", batch=8,
                                   kv_width=1024, weight_dtype="int4",
                                   kv_dtype="bfloat16"),
    "bf16_1b_8k": dict(model=LLAMA_1B, kind="decode", batch=1, kv_width=8704,
                       weight_dtype="bfloat16", kv_dtype="bfloat16"),
    "bf16_1b_8k_kvq8": dict(model=LLAMA_1B, kind="decode", batch=1,
                            kv_width=8704, weight_dtype="bfloat16",
                            kv_dtype="int8"),
    "bf16_1b_16k": dict(model=LLAMA_1B, kind="decode", batch=1,
                        kv_width=16896, weight_dtype="bfloat16",
                        kv_dtype="bfloat16"),
    "bf16_1b_16k_kvq8": dict(model=LLAMA_1B, kind="decode", batch=1,
                             kv_width=16896, weight_dtype="bfloat16",
                             kv_dtype="int8"),
}


def project_bench_row(name: str, device: Optional[DeviceSpec] = None) -> Optional[dict]:
    """Projected decode tok/s (device ceiling) for one bench row name; None
    for rows the table doesn't model. ``serving_spec`` rows project through
    the acceptance-parameterized speculative model."""
    row = BENCH_ROW_MODELS.get(name)
    if row is None:
        return None
    if row.get("kind") == "serving_spec":
        return spec_decode_projection(
            row["model"], batch=row["batch"], kv_width=row["kv_width"],
            acceptance=row["acceptance"], draft_len=row["draft_len"],
            draft_attrs=row.get("draft"),
            weight_dtype=row["weight_dtype"], kv_dtype=row["kv_dtype"],
            device=device,
        )
    return decode_projection(
        row["model"], batch=row["batch"], kv_width=row["kv_width"],
        weight_dtype=row["weight_dtype"], kv_dtype=row["kv_dtype"],
        device=device,
    )


#: bench summary-line key -> (row whose projection it compares against,
#: summary key holding the run's OWN recorded projection or None). A
#: recorded projection wins over the static table: the run knows things
#: the table cannot (e.g. the router row's count of non-overlapping
#: replica meshes on multi-chip hardware), so the bench row and the
#: --compare report can never disagree about the same run.
COMPARE_KEYS = (
    ("value", "bf16_1b_bs1", "projected_tok_s"),
    ("decode_bs4_tok_s", "bf16_1b_bs4", None),
    ("int8_1b_tok_s", "int8_1b_bs1", None),
    ("serving_tok_s", "serving_1b_int8", "serving_projected_tok_s"),
    ("ragged_tok_s", "serving_1b_int8_ragged", None),
    ("ragged_async_tok_s", "serving_1b_int8_ragged_async", None),
    # the spec row records its own projection: the bench re-projects at the
    # MEASURED acceptance rate, which the static table cannot know
    ("spec_ragged_tok_s", "serving_1b_int8_spec_ragged",
     "spec_ragged_projected_tok_s"),
    ("router_tok_s", "serving_1b_int8_router", "router_projected_tok_s"),
    ("router_threaded_tok_s", "serving_1b_int8_router_threaded", None),
    # goodput vs the same serving ceiling: the gap between goodput_tok_s
    # and the projection decomposes into (device gap) x (SLO attainment) —
    # the report line makes an SLO-driven collapse visible offline
    ("goodput_tok_s", "serving_1b_int8_goodput", None),
    ("int8_8b_tok_s", "int8_8b_bs1", None),
    # w4 rows record their own projections (the run re-derives them at the
    # measured shape), so the static table is the fallback comparator
    ("w4_tok_s", "bf16_8b_int4", "w4_projected_tok_s"),
    ("w4_serving_tok_s", "serving_1b_int4_ragged", "w4_serving_projected_tok_s"),
    ("ctx8k_tok_s", "bf16_1b_8k", None),
    ("kvq8_8k_tok_s", "bf16_1b_8k_kvq8", None),
    ("long_ctx_tok_s", "bf16_1b_16k", None),
    ("kvq8_16k_tok_s", "bf16_1b_16k_kvq8", None),
)


def compare_report(path: str) -> str:
    """Offline measured-vs-projected report over a committed bench summary
    (``BENCH_rNN.json`` — either the raw summary line or the driver wrapper
    with the summary under ``"parsed"``). Informational: per-row error
    fractions, no gate — hardware session zero's comparison tool."""
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict):
        raise ValueError(
            f"bench summary must be a JSON object, got {type(data).__name__}"
        )
    if isinstance(data.get("parsed"), dict):
        data = data["parsed"]
    device_str = str(data.get("device") or "")
    spec = resolve_device(device_str)
    resolved = spec is not None
    spec = spec or get_device()
    note = "" if resolved else (
        f", UNRESOLVED: projecting {DEFAULT_DEVICE} — errors are not meaningful"
    )
    lines = [
        f"measured-vs-projected (device {device_str or '<none>'} -> "
        f"{spec.name} spec{note})",
        f"  {'row':<30} {'measured':>10} {'projected':>10} {'err':>8}  bound",
    ]
    n = 0
    for key, row_name, recorded_key in COMPARE_KEYS:
        measured = data.get(key)
        if measured is None:
            continue
        proj = project_bench_row(row_name, spec)
        if proj is None:
            continue
        recorded = data.get(recorded_key) if recorded_key else None
        projected = recorded if recorded else proj["tok_s"]
        err = measured / projected - 1.0
        lines.append(
            f"  {row_name:<30} {measured:>10.1f} {projected:>10.1f} "
            f"{err:>+7.1%}  {proj['bound']}"
            f"{' (recorded)' if recorded else ''}"
        )
        n += 1
    if n == 0:
        lines.append("  (no comparable tok/s keys found in the summary)")
    lines.append(
        "projections are nameplate lower bounds on time: measured/projected"
        " - 1 near 0 means device-limited; strongly negative means a host "
        "gap or model error — see PERF.md 'Static roofline cost model'"
    )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# PERF.md table renderer
# ---------------------------------------------------------------------------


def _fmt_bytes(n: float) -> str:
    for unit, div in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if n >= div:
            return f"{n / div:.1f} {unit}"
    return f"{n:.0f} B"


def render_projection_tables(device: str = DEFAULT_DEVICE) -> str:
    """The markdown tables PERF.md commits (regenerate with
    ``python -m neuronx_distributed_inference_tpu.analysis.device_model``)."""
    spec = get_device(device)
    out = [
        f"<!-- generated by python -m neuronx_distributed_inference_tpu."
        f"analysis.device_model ({spec.name}) — edit the model, not the "
        f"table -->",
        "",
        f"Device: {spec.name} — bf16 peak "
        f"{spec.peak_flops['bfloat16'] / 1e12:.0f} TFLOP/s, int8 "
        f"{spec.peak_flops['int8'] / 1e12:.0f}, HBM "
        f"{spec.hbm_bw / 1e9:.0f} GB/s, ICI {spec.ici_bw / 1e9:.0f} GB/s, "
        f"VMEM {spec.vmem_bytes // (1024 ** 2)} MiB/core, "
        f"ridge {spec.ridge_flops_per_byte:.0f} FLOP/byte.",
        "",
        "| bench row | weights | KV read/step | bound | projected tok/s |",
        "|---|---|---|---|---|",
    ]
    for name, row in BENCH_ROW_MODELS.items():
        p = project_bench_row(name, spec)
        out.append(
            f"| {name} (bs={row['batch']}, kv {row['kv_width']}) | "
            f"{_fmt_bytes(p['weight_bytes'])} | "
            f"{_fmt_bytes(p['kv_read_bytes'])} | {p['bound']} | "
            f"{p['tok_s']:.0f} |"
        )
    out += [
        "",
        "| prefill | prompt | lower-bound wall | prefill tok/s ceiling |",
        "|---|---|---|---|",
    ]
    for name, attrs, seq in (
        ("1B bf16", LLAMA_1B, 512),
        ("1B bf16", LLAMA_1B, 2048),
        ("1B bf16", LLAMA_1B, 8192),
        ("1B bf16", LLAMA_1B, 16384),
        ("8B int8", LLAMA_8B, 512),
    ):
        p = prefill_projection(attrs, batch=1, seq=seq, device=spec)
        out.append(
            f"| {name} | {seq} | {p['t_pass_s'] * 1e3:.0f} ms | "
            f"{p['tok_s'] / 1e3:.1f}k |"
        )
    return "\n".join(out)


if __name__ == "__main__":  # pragma: no cover - exercised via PERF.md regen
    print(render_projection_tables())
