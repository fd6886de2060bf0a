"""TPU-native LLM inference framework (JAX / XLA / Pallas / pjit).

A ground-up re-design of the capabilities of NxD Inference
(reference: dacorvo/neuronx-distributed-inference) for TPU:

- ahead-of-time jit-compiled context-encoding / token-generation sub-models with
  sequence-length bucketing (reference: models/model_wrapper.py)
- GSPMD mesh parallelism: tp / cp / dp / ep axes over ICI (reference: process
  groups in modules/attention/attention_process_groups.py)
- donated in-place KV caches (reference: aliased KV buffers,
  models/model_wrapper.py:1673-1743)
- on-device sampling (reference: modules/generation/sampling.py)
- speculative decoding, MoE, quantization, LoRA (reference: §2.7/§2.6/§2.1)

Import as ``import neuronx_distributed_inference_tpu as nxdi_tpu``.
"""

__version__ = "0.1.0"

from neuronx_distributed_inference_tpu.config import (  # noqa: F401
    InferenceConfig,
    TpuConfig,
    OnDeviceSamplingConfig,
    FusedSpecConfig,
)
