"""Model hub. Each model plugin registers a builder keyed by HF model_type
(reference: utils/constants.py:42-53 model-type registry)."""

from neuronx_distributed_inference_tpu.models.registry import (  # noqa: F401
    MODEL_REGISTRY,
    get_model_builder,
    register_model,
)

# import plugins so they self-register
from neuronx_distributed_inference_tpu.models import llama  # noqa: F401
from neuronx_distributed_inference_tpu.models import qwen  # noqa: F401
from neuronx_distributed_inference_tpu.models import mixtral  # noqa: F401
from neuronx_distributed_inference_tpu.models import eagle_draft  # noqa: F401
from neuronx_distributed_inference_tpu.models import deepseek  # noqa: F401
from neuronx_distributed_inference_tpu.models import gpt_oss  # noqa: F401
from neuronx_distributed_inference_tpu.models import dbrx  # noqa: F401
from neuronx_distributed_inference_tpu.models import llama4  # noqa: F401
from neuronx_distributed_inference_tpu.models import granite_hybrid  # noqa: F401
from neuronx_distributed_inference_tpu.models import zaya  # noqa: F401
from neuronx_distributed_inference_tpu.models import nemotron_h  # noqa: F401
from neuronx_distributed_inference_tpu.models import sdar  # noqa: F401
from neuronx_distributed_inference_tpu.models import ouro  # noqa: F401
from neuronx_distributed_inference_tpu.models import glm_moe_dsa  # noqa: F401
from neuronx_distributed_inference_tpu.models import mellum  # noqa: F401
from neuronx_distributed_inference_tpu.models import kimi_linear  # noqa: F401
from neuronx_distributed_inference_tpu.models import brumby  # noqa: F401
