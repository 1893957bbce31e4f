"""Uniform model API: family registry + batch builders.

Every family module exposes:
  init(rng, cfg) -> params
  forward(params, cfg, batch, *, use_pallas=False) -> (logits, aux)
  loss_fn(params, cfg, batch, *, use_pallas=False) -> (loss, metrics)
  init_cache(cfg, batch, seq_len, dtype=None) -> cache
  decode_step(params, cfg, cache, tokens, *, use_pallas=False)
      -> (logits (B, V), cache)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.models import hymba, transformer, xlstm

_FAMILY = {
    "dense": transformer,
    "moe": transformer,
    "audio": transformer,
    "vlm": transformer,
    "ssm": xlstm,
    "hybrid": hymba,
}


def get_model(cfg: ModelConfig):
    return _FAMILY[cfg.family]


def init_params(rng, cfg: ModelConfig):
    return get_model(cfg).init(rng, cfg)


def loss_fn(params, cfg: ModelConfig, batch, *, use_pallas: bool = False):
    return get_model(cfg).loss_fn(params, cfg, batch, use_pallas=use_pallas)


def forward(params, cfg: ModelConfig, batch, *, use_pallas: bool = False):
    return get_model(cfg).forward(params, cfg, batch, use_pallas=use_pallas)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=None):
    return get_model(cfg).init_cache(cfg, batch, seq_len, dtype=dtype)


def decode_step(params, cfg: ModelConfig, cache, tokens, *, use_pallas=False):
    return get_model(cfg).decode_step(
        params, cfg, cache, tokens, use_pallas=use_pallas
    )


def prefill(params, cfg: ModelConfig, batch, cache_len: int, *,
            use_pallas=False):
    """Process a prompt batch -> (last-position logits, decode-ready cache)."""
    return get_model(cfg).prefill(
        params, cfg, batch, cache_len, use_pallas=use_pallas
    )


# ---------------------------------------------------------------------------
# batch construction (real arrays for smoke/train, ShapeDtypeStructs for
# abstract lowering)
# ---------------------------------------------------------------------------


def batch_shapes(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    """Abstract shapes of one *training* batch for this config."""
    shapes = {}
    if cfg.input_mode == "tokens":
        shapes["tokens"] = ((batch, seq_len), jnp.int32)
        shapes["labels"] = ((batch, seq_len), jnp.int32)
    elif cfg.input_mode == "embeddings":
        # audio stub: precomputed frame embeddings from the (stubbed)
        # conv/mel frontend
        shapes["embeddings"] = ((batch, seq_len, cfg.d_model), jnp.float32)
        shapes["labels"] = ((batch, seq_len), jnp.int32)
    elif cfg.input_mode == "tokens+patches":
        # vlm stub: ViT/projector output patch embeddings + text tokens
        shapes["patches"] = ((batch, cfg.num_patches, cfg.d_model), jnp.float32)
        shapes["tokens"] = ((batch, seq_len), jnp.int32)
        shapes["labels"] = ((batch, seq_len), jnp.int32)
    else:
        raise ValueError(cfg.input_mode)
    return shapes


def make_batch(rng, cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    """Concrete synthetic batch (used by smoke tests and examples)."""
    out = {}
    ks = jax.random.split(rng, 4)
    for i, (name, (shape, dtype)) in enumerate(batch_shapes(cfg, batch, seq_len).items()):
        if dtype == jnp.int32:
            arr = jax.random.randint(ks[i % 4], shape, 0, cfg.vocab_size, jnp.int32)
        else:
            arr = jax.random.normal(ks[i % 4], shape, jnp.float32) * 0.02
        out[name] = arr
    if cfg.is_encoder_only:
        # hubert-style masked prediction: ~8% of positions carry labels
        mask = jax.random.bernoulli(ks[3], 0.08, out["labels"].shape)
        out["labels"] = jnp.where(mask, out["labels"], -1)
    return out
