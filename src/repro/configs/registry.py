"""Architecture + input-shape registry.

Every assigned architecture registers its exact ArchConfig here (one module
per arch, citing its source).  ``input_specs`` builds weak-type-correct
ShapeDtypeStruct stand-ins for every model input of a given (arch, shape,
step) combination — the dry-run lowers against these without allocating.
"""
from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig

__all__ = ["get_config", "has_arch", "list_archs", "INPUT_SHAPES", "input_specs", "step_kind", "ARCH_MODULES"]

ARCH_MODULES = {
    "qwen3-moe-235b-a22b": "repro.configs.qwen3_moe_235b",
    "whisper-small": "repro.configs.whisper_small",
    "smollm-360m": "repro.configs.smollm_360m",
    "xlstm-125m": "repro.configs.xlstm_125m",
    "gemma2-27b": "repro.configs.gemma2_27b",
    "zamba2-1.2b": "repro.configs.zamba2_1p2b",
    "llama3.2-1b": "repro.configs.llama3_2_1b",
    "llama3-405b": "repro.configs.llama3_405b",
    "arctic-480b": "repro.configs.arctic_480b",
    "llama-3.2-vision-11b": "repro.configs.llama3_2_vision_11b",
    "granite-4.0-h-micro": "repro.configs.granite_4_0_h_micro",
    "granite-4.0-h-micro-p1": "repro.configs.granite_4_0_h_micro_p1",
}


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def get_config(name: str) -> ArchConfig:
    if name not in ARCH_MODULES:
        raise ValueError(f"unknown arch {name!r}; options: {list_archs()}")
    mod = importlib.import_module(ARCH_MODULES[name])
    return mod.CONFIG


def has_arch(name: str) -> bool:
    """Whether ``name`` is a registered zoo architecture (spec validation)."""
    return name in ARCH_MODULES


def list_archs() -> list[str]:
    return list(ARCH_MODULES)


def step_kind(cfg: ArchConfig, shape: InputShape) -> str | None:
    """Which step a (arch, shape) pair lowers — None means 'skip' (recorded
    in DESIGN.md section 4: long_500k only for sub-quadratic archs)."""
    if shape.name == "long_500k" and not cfg.long_context_ok:
        return None
    return shape.kind


def _aux_embed_spec(cfg: ArchConfig, batch: int):
    if not cfg.frontend:
        return None
    fd = cfg.frontend_dim or cfg.d_model
    return jax.ShapeDtypeStruct((batch, cfg.frontend_seq, fd), jnp.float32)


def input_specs(cfg: ArchConfig, shape: InputShape, cohort: int = 1) -> dict:
    """Abstract inputs for one step.

    train:   tokens/targets (global_batch, seq) [+ frontend embeds]
    prefill: tokens (global_batch, seq) [+ frontend embeds]
    decode:  token (global_batch, 1) + caches(seq_len) + index
    """
    kind = step_kind(cfg, shape)
    if kind is None:
        raise ValueError(f"{cfg.name} skips {shape.name}")
    tok = jnp.int32
    b, s = shape.global_batch, shape.seq_len
    if kind == "train":
        specs = {
            "tokens": jax.ShapeDtypeStruct((b, s), tok),
            "targets": jax.ShapeDtypeStruct((b, s), tok),
        }
        aux = _aux_embed_spec(cfg, b)
        if aux is not None:
            specs["aux_embeds"] = aux
        return specs
    if kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), tok)}
        aux = _aux_embed_spec(cfg, b)
        if aux is not None:
            specs["aux_embeds"] = aux
        return specs
    # decode: abstract caches via eval_shape (no allocation)
    from repro.models import transformer

    caches = jax.eval_shape(lambda: transformer.init_caches(cfg, b, s))
    return {
        "token": jax.ShapeDtypeStruct((b, 1), tok),
        "caches": caches,
        "index": jax.ShapeDtypeStruct((), tok),
    }
