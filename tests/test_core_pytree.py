"""Aggregation math vs. a plain-numpy oracle (the reference's key-by-key loop,
FedAVGAggregator.py:58-87)."""

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.core import (
    tree_weighted_mean, tree_global_norm, tree_sub,
)
from fedml_tpu.core.pytree import tree_weighted_psum_mean
from fedml_tpu.core.robust import clip_update, add_gaussian_noise


def _random_tree(rng, scale=1.0):
    return {
        "dense": {"w": rng.randn(4, 3).astype(np.float32) * scale,
                  "b": rng.randn(3).astype(np.float32) * scale},
        "out": rng.randn(3, 2).astype(np.float32) * scale,
    }


def _numpy_weighted_mean(trees, ns):
    total = sum(ns)
    out = jax.tree.map(lambda *xs: sum(x * (n / total) for x, n in zip(xs, ns)), *trees)
    return out


def test_weighted_mean_matches_reference_loop(rng):
    trees = [_random_tree(rng) for _ in range(5)]
    ns = [3, 10, 1, 7, 4]
    got = tree_weighted_mean(trees, jnp.array(ns))
    want = _numpy_weighted_mean(trees, ns)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6), got, want)


def test_weighted_mean_stacked_layout(rng):
    trees = [_random_tree(rng) for _ in range(4)]
    ns = jnp.array([1.0, 2.0, 3.0, 4.0])
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    got = tree_weighted_mean(stacked, ns)
    want = tree_weighted_mean(trees, ns)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6), got, want)


def test_weighted_mean_is_jittable(rng):
    trees = [_random_tree(rng) for _ in range(3)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    f = jax.jit(tree_weighted_mean)
    got = f(stacked, jnp.array([1.0, 1.0, 2.0]))
    want = tree_weighted_mean(stacked, jnp.array([1.0, 1.0, 2.0]))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6), got, want)


def test_global_norm(rng):
    t = _random_tree(rng)
    flat = np.concatenate([np.ravel(x) for x in jax.tree.leaves(t)])
    np.testing.assert_allclose(tree_global_norm(t), np.linalg.norm(flat), rtol=1e-5, atol=1e-6)


def test_psum_mean_matches_local_mean(rng, devices):
    """Distributed weighted mean over an 8-device mesh == the list version."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    trees = [_random_tree(rng) for _ in range(8)]
    ns = np.array([5., 1., 2., 8., 3., 4., 6., 7.], np.float32)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    mesh = Mesh(np.array(devices), ("clients",))

    @jax.jit
    def run(stacked, ns):
        def per_device(tree_slice, n):
            local = jax.tree.map(lambda x: x[0], tree_slice)
            return tree_weighted_psum_mean(local, n[0], "clients")
        return shard_map(
            per_device, mesh=mesh,
            in_specs=(P("clients"), P("clients")),
            out_specs=P())(stacked, ns)

    got = run(stacked, ns)
    want = tree_weighted_mean(trees, ns)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
                 got, want)


def test_clip_update_norm_bound(rng):
    g = _random_tree(rng)
    c = _random_tree(rng, scale=10.0)
    clipped = clip_update(c, g, norm_bound=1.0)
    diff_norm = tree_global_norm(tree_sub(clipped, g))
    assert float(diff_norm) <= 1.0 + 1e-4
    # inside the bound: untouched
    near = jax.tree.map(lambda x: x + 1e-4, g)
    kept = clip_update(near, g, norm_bound=1.0)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6), kept, near)


def test_add_noise_stddev(rng):
    t = {"w": jnp.zeros((200, 200))}
    noised = add_gaussian_noise(t, jax.random.key(0), stddev=0.5)
    assert abs(float(jnp.std(noised["w"])) - 0.5) < 0.02
