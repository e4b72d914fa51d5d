"""Model bundles: uniform (init / loss / forward / prefill / decode / specs)
surface consumed by launch/steps.py, the dry-run, tests and benchmarks.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import encdec, transformer
from repro.models.config import ModelConfig, ShapeSpec
from repro.utils import trees

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ModelConfig
    init: Callable[[jax.Array], Pytree]
    forward: Callable[[Pytree, dict], tuple[jax.Array, jax.Array]]
    prefill: Callable[..., tuple[jax.Array, Pytree]]
    decode: Callable[[Pytree, Pytree, dict], tuple[jax.Array, Pytree]]
    init_cache: Callable[..., Pytree]

    def loss_fn(self, params: Pytree, batch: dict, rng: jax.Array
                ) -> tuple[jax.Array, dict]:
        """Next-token cross entropy + MoE aux loss (the repro.core protocol)."""
        logits, aux_loss = self.forward(params, batch)
        ce = cross_entropy(logits, batch["labels"])
        return ce + aux_loss, {"ce": ce, "moe_aux": aux_loss, "logits": logits}


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Stable CE over a (possibly vocab-sharded) logits tensor; labels<0 masked.

    The label logit is picked with an iota==label masked sum instead of
    take_along_axis: elementwise ops preserve the vocab ("model"-axis) sharding
    under pjit, where a gather would all-gather the full-vocab logits per
    device (observed 80+GB/device in the dry-run).
    """
    with jax.named_scope("cross_entropy"):
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape,
                                              lf.ndim - 1)
        onehot = (vocab_iota == jnp.maximum(labels, 0)[..., None])
        picked = jnp.sum(jnp.where(onehot, lf, 0.0), axis=-1)
        mask = (labels >= 0).astype(jnp.float32)
        return (jnp.sum((lse - picked) * mask)
                / jnp.maximum(jnp.sum(mask), 1.0))


def build_model(cfg: ModelConfig) -> ModelBundle:
    if cfg.family == "audio":
        return ModelBundle(
            cfg=cfg,
            init=lambda key: encdec.init_params(key, cfg),
            forward=lambda p, b: encdec.forward(p, b, cfg),
            prefill=lambda p, b, pad_to=0: encdec.prefill(p, b, cfg, pad_to=pad_to),
            decode=lambda p, c, b: encdec.decode(p, c, b, cfg),
            init_cache=lambda batch, max_len, pos=0: _encdec_cache(cfg, batch,
                                                                   max_len, pos),
        )
    return ModelBundle(
        cfg=cfg,
        init=lambda key: transformer.init_params(key, cfg),
        forward=lambda p, b: transformer.forward(p, b, cfg),
        prefill=lambda p, b, pad_to=0: transformer.prefill(p, b, cfg, pad_to=pad_to),
        decode=lambda p, c, b: transformer.decode(p, c, b, cfg),
        init_cache=lambda batch, max_len, pos=0: transformer.init_cache(
            cfg, batch, max_len, pos),
    )


def _encdec_cache(cfg: ModelConfig, batch: int, max_len: int, pos: int) -> Pytree:
    cdt = jnp.dtype(cfg.compute_dtype)
    hd = cfg.resolved_head_dim
    enc_len = whisper_enc_len(cfg, max_len)
    layer = {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), cdt),
             "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), cdt),
             "cross_k": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), cdt),
             "cross_v": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), cdt)}
    layers = jax.tree.map(lambda a: jnp.broadcast_to(a, (cfg.n_layers, *a.shape)),
                          layer)
    return {"layers": layers, "pos": jnp.asarray(pos, jnp.int32)}


def whisper_enc_len(cfg: ModelConfig, dec_len: int) -> int:
    """Encoder frames per cell: whisper's native 1500 for decode cells, the
    cell's seq_len for train/prefill stress shapes (DESIGN.md §4)."""
    return min(int(dec_len * cfg.encdec.enc_len_ratio), dec_len)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs for the dry-run) and concrete batch synthesis
# ---------------------------------------------------------------------------

def batch_spec(cfg: ModelConfig, shape: ShapeSpec,
               ascent_fraction: float = 0.0) -> dict:
    """Abstract train/prefill batch (ShapeDtypeStruct leaves)."""
    b, s = shape.global_batch, shape.seq_len
    spec = _one_batch_spec(cfg, b, s)
    if shape.kind == "train" and ascent_fraction > 0:
        bp = max(1, int(round(b * ascent_fraction)))
        spec["ascent"] = _one_batch_spec(cfg, bp, s)
    return spec


def _one_batch_spec(cfg: ModelConfig, b: int, s: int) -> dict:
    i32 = jnp.int32
    cdt = jnp.dtype(cfg.compute_dtype)
    spec = {"tokens": jax.ShapeDtypeStruct((b, s), i32),
            "labels": jax.ShapeDtypeStruct((b, s), i32)}
    if cfg.vision is not None:
        spec["patch_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.vision.n_image_tokens, cfg.vision.clip_dim), cdt)
    if cfg.family == "audio":
        spec["enc_frames"] = jax.ShapeDtypeStruct(
            (b, whisper_enc_len(cfg, s), cfg.d_model), cdt)
    return spec


def decode_batch_spec(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    return {"tokens": jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)}


def cache_spec(cfg: ModelConfig, shape: ShapeSpec) -> Pytree:
    """Abstract decode cache with pos = seq_len - 1 (one slot left)."""
    bundle_cache = jax.eval_shape(
        lambda: build_model(cfg).init_cache(shape.global_batch, shape.seq_len,
                                            pos=shape.seq_len - 1))
    return bundle_cache


def synth_batch(cfg: ModelConfig, b: int, s: int, key: jax.Array,
                ascent_fraction: float = 0.0) -> dict:
    """Concrete random batch matching batch_spec (smoke tests, benchmarks)."""
    k1, k2, k3 = jax.random.split(key, 3)
    batch = _synth_one(cfg, b, s, k1)
    if ascent_fraction > 0:
        bp = max(1, int(round(b * ascent_fraction)))
        batch["ascent"] = _synth_one(cfg, bp, s, k2)
    return batch


def _synth_one(cfg: ModelConfig, b: int, s: int, key: jax.Array) -> dict:
    k1, k2 = jax.random.split(key)
    tokens = jax.random.randint(k1, (b, s), 0, cfg.vocab_size, jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)
    batch = {"tokens": tokens, "labels": labels}
    cdt = jnp.dtype(cfg.compute_dtype)
    if cfg.vision is not None:
        batch["patch_embeds"] = jax.random.normal(
            k2, (b, cfg.vision.n_image_tokens, cfg.vision.clip_dim), cdt)
    if cfg.family == "audio":
        batch["enc_frames"] = jax.random.normal(
            k2, (b, whisper_enc_len(cfg, s), cfg.d_model), cdt)
    return batch


# ---------------------------------------------------------------------------
# Analytic parameter counts (roofline 6ND sanity)
# ---------------------------------------------------------------------------

def analytic_param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact count via abstract init; `active_only` subtracts inactive experts."""
    bundle = build_model(cfg)
    shapes = jax.eval_shape(lambda: bundle.init(jax.random.PRNGKey(0)))
    total = trees.tree_size(shapes)
    if active_only and cfg.moe is not None:
        e, k = cfg.moe.n_experts, cfg.moe.top_k
        expert_params = 3 * cfg.d_model * cfg.moe.expert_d_ff
        n_moe_layers = cfg.n_layers - cfg.moe.first_dense_layers
        total -= n_moe_layers * (e - k) * expert_params
    return int(total)
