"""TPU device-spec registry + the analytic roofline projection model.

One table of nameplate numbers (peak FLOP/s by dtype, HBM GB/s, ICI GB/s,
VMEM per core) and one set of closed-form llama-shaped cost formulas, so
the repo has a single source of truth for "how fast should this be":

- :mod:`.cost_audit` projects a lower-bound step time / tok/s for every
  audited (family, bucket) program from its HLO-derived FLOPs/bytes census;
- :mod:`.kernel_audit` budgets every kernel's VMEM against the device's;
- ``chip_smoke.py`` resolves the chip it found against the registry;
- ``python -m neuronx_distributed_inference_tpu.analysis.device_model``
  prints the device line and the prefill projection table.

The registry numbers are NAMEPLATE (vendor peak). Measured efficiency on
this stack is ~67–92% of nameplate depending on op mix (PERF.md rounds
2–5); projections here are therefore LOWER BOUNDS on time (upper bounds on
tok/s), which is exactly what a regression gate wants: a measured number
can approach the bound but a model change that moves the bound itself must
be reviewed.
"""

from __future__ import annotations

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

#: the benchmark's chip — projections on a host with no resolvable TPU (the
#: CPU harness) are computed against this spec
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
# model shapes
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


# ---------------------------------------------------------------------------
# table renderer
# ---------------------------------------------------------------------------


def render_projection_tables(device: str = DEFAULT_DEVICE) -> str:
    """The device line and the prefill projections as markdown
    (``python -m neuronx_distributed_inference_tpu.analysis.device_model``)."""
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


if __name__ == "__main__":  # pragma: no cover
    print(render_projection_tables())
