"""Input snapshots, divergence auto-capture, replay, and debug IO logging.

TPU-native re-design of the reference debug stack
(reference: utils/snapshot.py ScriptModuleWrapper input capture;
utils/debug_utils.py capture_model_inputs; inference_demo.py:329-334
--capture-indices / --input-capture-save-dir; :600-614 auto-capture on
logit-matching failure).

The reference wraps traced ScriptModules with forward hooks; here the hook
wraps the SubModelRunner's jitted call — every capture is a plain ``.npz``
of the exact StepInputs pytree, replayable offline with
:func:`replay_snapshot` on any backend.
"""

from __future__ import annotations

import logging
import os
from typing import Dict, List, Optional

import numpy as np

logger = logging.getLogger("nxdi_tpu.debug")

if os.environ.get("NXDI_TPU_DEBUG") == "1":  # pragma: no cover - env wiring
    logger.setLevel(logging.DEBUG)
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[%(name)s] %(message)s"))
    logger.addHandler(_h)

_FIELDS = (
    "input_ids",
    "attention_mask",
    "position_ids",
    "seq_ids",
    "sampling_params",
    "slot_mapping",
    "block_table",
    "adapter_ids",
)


def save_inputs_snapshot(inputs, path: str, step: Optional[int] = None, tag: str = ""):
    """Persist one step's StepInputs as .npz (reference
    debug_utils.capture_model_inputs)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    arrs: Dict[str, np.ndarray] = {}
    for f in _FIELDS:
        v = getattr(inputs, f, None)
        if v is not None:
            arrs[f] = np.asarray(v)
    meta = {"step": -1 if step is None else step, "tag": tag}
    np.savez(path, __meta_step=np.int64(meta["step"]), __meta_tag=np.bytes_(tag), **arrs)
    logger.info("saved input snapshot %s (step=%s tag=%s)", path, step, tag)


def load_inputs_snapshot(path: str):
    """Load a snapshot back into StepInputs (+ meta dict)."""
    import jax.numpy as jnp

    from neuronx_distributed_inference_tpu.models.base import StepInputs

    with np.load(path, allow_pickle=False) as z:
        kwargs = {f: jnp.asarray(z[f]) for f in _FIELDS if f in z.files}
        meta = {
            "step": int(z["__meta_step"]) if "__meta_step" in z.files else -1,
            "tag": bytes(z["__meta_tag"]).decode() if "__meta_tag" in z.files else "",
        }
    return StepInputs(**kwargs), meta


class InputCaptureHook:
    """Capture StepInputs flowing through an app's runners
    (reference ScriptModuleWrapper + capture_model_inputs).

    ``capture_indices=None`` captures every dispatch; otherwise only the
    listed global dispatch indices. Install with :func:`install_input_capture`.
    """

    def __init__(self, save_dir: str, capture_indices: Optional[List[int]] = None):
        self.save_dir = save_dir
        self.capture_indices = set(capture_indices) if capture_indices is not None else None
        self.count = 0
        self.saved: List[str] = []

    def __call__(self, tag: str, inputs):
        idx = self.count
        self.count += 1
        if self.capture_indices is not None and idx not in self.capture_indices:
            return
        path = os.path.join(self.save_dir, f"{idx:05d}_{tag}.npz")
        save_inputs_snapshot(inputs, path, step=idx, tag=tag)
        self.saved.append(path)

    def chunk(self, tag, last, pos, seq_ids, sampling_params, num_steps, bucket):
        """Capture a multi-step decode-chunk dispatch (decode_steps program)."""
        idx = self.count
        self.count += 1
        if self.capture_indices is not None and idx not in self.capture_indices:
            return
        path = os.path.join(self.save_dir, f"{idx:05d}_{tag}.chunk.npz")
        os.makedirs(self.save_dir, exist_ok=True)
        np.savez(
            path,
            __chunk=np.int64(1),
            __num_steps=np.int64(num_steps),
            __bucket=np.int64(bucket),
            __meta_tag=np.bytes_(tag),
            last=np.asarray(last),
            pos=np.asarray(pos),
            seq_ids=np.asarray(seq_ids),
            sampling_params=np.asarray(sampling_params),
        )
        logger.info("saved chunk snapshot %s (steps=%s bucket=%s)", path, num_steps, bucket)
        self.saved.append(path)


def install_input_capture(app, save_dir: str, capture_indices=None) -> InputCaptureHook:
    """Wrap the app's runners so every jitted dispatch snapshots its inputs.

    Returns the hook (``hook.saved`` lists written files). Uninstall with
    :func:`uninstall_input_capture`.
    """
    hook = InputCaptureHook(save_dir, capture_indices)
    for runner in app.runners:
        orig = runner._fn

        def wrapped(params, cache, inputs, rng=None, _orig=orig, _tag=runner.tag):
            hook(_tag, inputs)
            return _orig(params, cache, inputs, rng)

        runner._capture_orig_fn = orig
        runner._fn = wrapped

        orig_dc = runner.decode_chunk

        def wrapped_dc(*args, _orig=orig_dc, _tag=runner.tag, **kwargs):
            # args: params, cache, last, pos, seq_ids, sampling_params, rng
            hook.chunk(
                _tag, args[2], args[3], args[4], args[5],
                kwargs.get("num_steps"), kwargs.get("bucket"),
            )
            return _orig(*args, **kwargs)

        runner.decode_chunk = wrapped_dc
    app._input_capture_hook = hook
    return hook


def uninstall_input_capture(app):
    for runner in app.runners:
        orig = getattr(runner, "_capture_orig_fn", None)
        if orig is not None:
            runner._fn = orig
            del runner._capture_orig_fn
        if "decode_chunk" in runner.__dict__:
            del runner.__dict__["decode_chunk"]
    app._input_capture_hook = None


def replay_snapshot(app, path: str):
    """Re-run one captured dispatch offline: load the snapshot, pick the
    runner by the tag embedded in the filename, and execute it against the
    app's current params/cache (reference: re-feeding captured inputs to a
    traced model). Returns the StepOutput (or the decode-chunk triple).

    The app's live cache is COPIED first — runner programs donate their cache
    argument, and replay must not consume serving state."""
    import jax
    import jax.numpy as jnp

    replay_cache = jax.tree.map(jnp.copy, app.kv_cache)
    with np.load(path, allow_pickle=False) as z:
        is_chunk = "__chunk" in z.files
        if is_chunk:
            tag = bytes(z["__meta_tag"]).decode()
            payload = {k: z[k] for k in ("last", "pos", "seq_ids", "sampling_params")}
            num_steps = int(z["__num_steps"])
            bucket = int(z["__bucket"])
    if is_chunk:
        for runner in app.runners:
            if runner.tag == tag:
                return runner.decode_chunk(
                    app.params, replay_cache, payload["last"], payload["pos"],
                    payload["seq_ids"], payload["sampling_params"], None,
                    num_steps=num_steps, bucket=bucket,
                )
        raise ValueError(f"no runner with tag {tag!r} (snapshot {path})")
    inputs, meta = load_inputs_snapshot(path)
    tag = meta["tag"] or os.path.basename(path).split("_", 1)[-1].rsplit(".", 1)[0]
    for runner in app.runners:
        if runner.tag == tag:
            return runner(app.params, replay_cache, inputs, None)
    raise ValueError(f"no runner with tag {tag!r} (snapshot {path})")


# ---------------------------------------------------------------------------
# debug in/out logging (reference debug input/output logging)
# ---------------------------------------------------------------------------


def enable_debug_logging(level=logging.DEBUG):
    """Log every runner dispatch's input shapes/ids and output tokens.

    Also enabled by setting NXDI_TPU_DEBUG=1 in the environment before the
    app is constructed."""
    logger.setLevel(level)
    if not logger.handlers:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter("[%(name)s] %(message)s"))
        logger.addHandler(h)


def debug_log_step(tag: str, inputs, output=None):
    if not logger.isEnabledFor(logging.DEBUG):
        return
    ids = np.asarray(inputs.input_ids)
    pos = np.asarray(inputs.position_ids)
    logger.debug(
        "%s: ids%s pos[min=%d,max=%d] seq_ids=%s",
        tag, ids.shape, pos.min(), pos.max(), np.asarray(inputs.seq_ids).tolist(),
    )
    if output is not None and getattr(output, "tokens", None) is not None:
        logger.debug("%s -> tokens %s", tag, np.asarray(output.tokens)[:, :8].tolist())


# ---------------------------------------------------------------------------
# KV cache reconstruction (reference utils/kv_cache_reconstruct_utils.py)
# ---------------------------------------------------------------------------


def reconstruct_kv_cache(app, token_history, attention_mask=None, lora_adapter_names=None):
    """Rebuild the app's KV cache from a token history — e.g. to resume a
    preempted/restored request without the original cache (reference
    kv_cache_reconstruct_utils.py: replay prompt+generated tokens through
    context encoding).

    ``token_history``: (B, S) everything decoded so far (prompt + generated),
    RIGHT-PACKED per row (each row's valid tokens contiguous from position 0 —
    generated tokens directly follow the prompt, as serving histories are).
    Returns the per-row next write position. The app's cache is replaced.

    Runs through the app's own windowed-prefill path, so histories longer
    than one CTE program (or the ring window) reconstruct the same way
    generate() would prefill them.
    """
    from neuronx_distributed_inference_tpu.modules.sampling import (
        prepare_sampling_params,
    )

    tc = app.config.tpu_config
    if tc.is_block_kv_layout:
        raise NotImplementedError(
            "block-KV reconstruction replays through ServingSession "
            "re-admission (add_request with the history as the prompt)"
        )
    token_history = np.asarray(token_history)
    if attention_mask is None:
        attention_mask = np.ones_like(token_history)
    attention_mask = np.asarray(attention_mask)
    B, S = token_history.shape
    # generate()'s own pre-checks, run BEFORE wiping the live cache
    app.validate_prefill_length(S)
    adapter_ids = app.resolve_adapter_ids(lora_adapter_names)
    app.init_kv_cache()  # fresh lines
    # _windowed_prefill degenerates to a single CTE pass when the history
    # fits one program — one shared prefill path, one set of guards
    app._windowed_prefill(
        token_history, attention_mask, np.arange(B, dtype=np.int32),
        prepare_sampling_params(B), adapter_ids,
    )
    return attention_mask.sum(axis=1).astype(np.int64)
