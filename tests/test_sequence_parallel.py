"""Sequence-parallel attention: ring (ppermute) and Ulysses (all-to-all).

Both schemes shard the sequence axis over an `sp` mesh axis inside
shard_map and must match full (unsharded) mha numerically — exceeding the
reference, which has no sequence parallelism at all (SURVEY.md §5.7).
Runs on the virtual 8-device CPU mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from determined_clone_tpu.ops.attention import (
    mha,
    ring_attention,
    ulysses_attention,
)

SP = 4
B, T, H, D = 2, 256, 8, 32


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices()[:SP]).reshape(SP)
    return Mesh(devs, ("sp",))


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return tuple(jax.random.normal(k, (B, T, H, D), jnp.float32) for k in ks)


def test_ring_matches_full(mesh, qkv):
    q, k, v = qkv
    spec = P(None, "sp")

    def local(q, k, v):
        idx = jax.lax.axis_index("sp")
        return ring_attention(q, k, v, axis_name="sp", axis_index=idx,
                              axis_size=SP)

    f = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                  out_specs=spec)
    out = jax.jit(f)(q, k, v)
    ref = mha(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_matches_full(mesh, qkv):
    q, k, v = qkv
    spec = P(None, "sp")

    def local(q, k, v):
        return ulysses_attention(q, k, v, axis_name="sp", causal=True)

    f = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                  out_specs=spec)
    out = jax.jit(f)(q, k, v)
    ref = mha(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_gradients_match_full(mesh, qkv):
    q, k, v = qkv
    spec = P(None, "sp")

    def sp_loss(q, k, v):
        f = shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return (f(q, k, v) ** 2).sum()

    def ref_loss(q, k, v):
        return (mha(q, k, v, causal=True) ** 2).sum()

    g_sp = jax.jit(jax.grad(sp_loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_sp, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_ulysses_requires_divisible_heads(mesh):
    # H=6 not divisible by sp=4: all_to_all must reject, not silently skew
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(key, (B, T, 6, D)) for key in ks)
    spec = P(None, "sp")
    f = shard_map(lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
                  mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    with pytest.raises(Exception):
        jax.jit(f)(q, k, v)
