"""Functional NN layers: pure init/apply functions over parameter pytrees.

The reference delegates all numerics to PyTorch/TF (SURVEY.md preamble); this
framework owns them, XLA-first: params are plain pytrees of jnp arrays,
every layer is a pure function, and dtype policy is bf16-compute/fp32-params
by default (the TPU analogue of the reference's AMP path,
harness/determined/pytorch/_pytorch_trial.py:872).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def trunc_normal(key: jax.Array, shape: Tuple[int, ...], stddev: float = 0.02,
                 dtype=jnp.float32) -> jax.Array:
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)

def lecun_normal(key: jax.Array, shape: Tuple[int, ...], fan_in: Optional[int] = None,
                 dtype=jnp.float32) -> jax.Array:
    fan_in = fan_in if fan_in is not None else shape[0]
    return trunc_normal(key, shape, stddev=math.sqrt(1.0 / max(1, fan_in)), dtype=dtype)

def he_normal(key: jax.Array, shape: Tuple[int, ...], fan_in: Optional[int] = None,
              dtype=jnp.float32) -> jax.Array:
    fan_in = fan_in if fan_in is not None else shape[0]
    return trunc_normal(key, shape, stddev=math.sqrt(2.0 / max(1, fan_in)), dtype=dtype)


# ---------------------------------------------------------------------------
# Dense / embedding / norms
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, in_dim: int, out_dim: int, *, bias: bool = True,
               dtype=jnp.float32) -> Params:
    p: Params = {"kernel": lecun_normal(key, (in_dim, out_dim), dtype=dtype)}
    if bias:
        p["bias"] = jnp.zeros((out_dim,), dtype)
    return p

def dense(params: Params, x: jax.Array, *, compute_dtype=None) -> jax.Array:
    k = params["kernel"]
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        k = k.astype(compute_dtype)
    y = x @ k
    if "bias" in params:
        y = y + params["bias"].astype(y.dtype)
    return y


def embedding_init(key: jax.Array, vocab: int, dim: int, dtype=jnp.float32) -> Params:
    return {"table": trunc_normal(key, (vocab, dim), dtype=dtype)}

def embedding(params: Params, ids: jax.Array, *, compute_dtype=None) -> jax.Array:
    t = params["table"]
    if compute_dtype is not None:
        t = t.astype(compute_dtype)
    return jnp.take(t, ids, axis=0)


def layernorm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}

def layernorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    # Norm statistics in fp32 regardless of activation dtype (TPU numerics rule).
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rmsnorm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype)}

def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-6, *,
            unit_offset: bool = False, dtype=None) -> jax.Array:
    """``unit_offset``: the stored scale is w and the norm multiplies by
    1 + w (a zero-initialised w is the identity). ``dtype``: of the
    result, x's by default; statistics are fp32 either way."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    scale = params["scale"].astype(jnp.float32)
    y = xf * jax.lax.rsqrt(ms + eps) * (1.0 + scale if unit_offset else scale)
    return y.astype(dtype or x.dtype)


# ---------------------------------------------------------------------------
# Convolutions (for the ResNet / mnist-CNN families)
# ---------------------------------------------------------------------------

def conv_init(key: jax.Array, in_ch: int, out_ch: int, kernel: int, *,
              dtype=jnp.float32) -> Params:
    shape = (kernel, kernel, in_ch, out_ch)  # HWIO
    return {"kernel": he_normal(key, shape, fan_in=kernel * kernel * in_ch, dtype=dtype)}

def conv2d(params: Params, x: jax.Array, *, stride: int = 1, padding: str = "SAME",
           compute_dtype=None) -> jax.Array:
    """NHWC conv — the TPU-native layout (channels on the 128-lane minor dim)."""
    k = params["kernel"]
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        k = k.astype(compute_dtype)
    return jax.lax.conv_general_dilated(
        x, k, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def batchnorm_init(ch: int, dtype=jnp.float32) -> Params:
    return {
        "scale": jnp.ones((ch,), dtype), "bias": jnp.zeros((ch,), dtype),
        "mean": jnp.zeros((ch,), dtype), "var": jnp.ones((ch,), dtype),
    }

def batchnorm(params: Params, x: jax.Array, *, training: bool, momentum: float = 0.9,
              eps: float = 1e-5, axis_name: Optional[str] = None,
              ) -> Tuple[jax.Array, Params]:
    """BatchNorm with functional running-stat updates. Under pjit the batch
    dims are sharded; statistics computed with jnp.mean are automatically
    global because XLA inserts the cross-device reduction (no explicit psum
    needed unless inside shard_map, where axis_name applies)."""
    xf = x.astype(jnp.float32)
    if training:
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(xf, axis=axes)
        var = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(mean)
        if axis_name is not None:
            mean = jax.lax.pmean(mean, axis_name)
            var = jax.lax.pmean(var, axis_name)
        new_stats = {
            **params,
            "mean": momentum * params["mean"] + (1 - momentum) * mean,
            "var": momentum * params["var"] + (1 - momentum) * var,
        }
    else:
        mean, var = params["mean"], params["var"]
        new_stats = params
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"] + params["bias"]
    return y.astype(x.dtype), new_stats


def groupnorm_init(ch: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((ch,), dtype), "bias": jnp.zeros((ch,), dtype)}


def groupnorm(params: Params, x: jax.Array, *, groups: int = 32,
              eps: float = 1e-5) -> jax.Array:
    """GroupNorm over NHWC. Batch-size independent — the TPU-friendly norm
    for conv nets: no running stats to thread functionally and no
    cross-replica sync dependence, so per-device batch size never changes
    the math (the reason ResNet-50-GN recipes exist)."""
    B, H, W, C = x.shape
    g = min(groups, C)
    while C % g != 0:  # channel counts not divisible by 32 (stems, tests)
        g -= 1
    xf = x.astype(jnp.float32).reshape(B, H, W, g, C // g)
    mean = jnp.mean(xf, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=(1, 2, 4), keepdims=True)
    y = ((xf - mean) * jax.lax.rsqrt(var + eps)).reshape(B, H, W, C)
    y = y * params["scale"] + params["bias"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations / misc
# ---------------------------------------------------------------------------

def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True)

def dropout(key: Optional[jax.Array], x: jax.Array, rate: float,
            training: bool) -> jax.Array:
    if not training or rate <= 0.0 or key is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          label_smoothing: float = 0.0) -> jax.Array:
    """Per-example loss; logits [..., C], integer labels [...]. Computed in
    fp32 (logit dtype may be bf16)."""
    logits = logits.astype(jnp.float32)
    n_classes = logits.shape[-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logit = jnp.take_along_axis(
        logits, labels[..., None], axis=-1
    ).squeeze(-1)
    loss = logz - label_logit
    if label_smoothing > 0.0:
        smooth = -jnp.mean(logits, axis=-1) + logz
        loss = (1 - label_smoothing) * loss + label_smoothing * smooth
    return loss


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
