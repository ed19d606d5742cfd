"""Gradient compression: quantization error bounds, error feedback,
convergence, and the shard_map DP-reduction pattern."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
from _hyp_compat import given, settings, st

from repro.optim.compression import (CompressedGrad, compression_ratio,
                                     dequantize, quantize, tree_dequantize,
                                     tree_quantize)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000), st.floats(1e-4, 1e3))
def test_quantize_error_bound(seed, scale):
    g = jax.random.normal(jax.random.PRNGKey(seed), (257,)) * scale
    c, res = quantize(g)
    err = jnp.abs(dequantize(c) - g)
    assert float(jnp.max(err)) <= float(c.scale) * 0.5 + 1e-9
    # residual == the quantization error (carried forward)
    np.testing.assert_allclose(res, g - dequantize(c), rtol=1e-5, atol=1e-8)


def test_error_feedback_removes_bias():
    """With error feedback, the accumulated dequantized sum tracks the true
    gradient sum even when each step's gradient is below one quantum."""
    g = jnp.full((64,), 1e-3)
    big = jnp.zeros((64,)).at[0].set(1.0)      # forces a coarse scale
    res = jnp.zeros((64,))
    acc = jnp.zeros((64,))
    for _ in range(100):
        c, res = quantize(g + big * 0.0, res)  # scale set by residual growth
        acc = acc + dequantize(c)
    np.testing.assert_allclose(acc[1:], 100 * g[1:], rtol=0.05)


def test_sgd_with_compression_converges():
    w = jnp.array([2.0, -3.0, 1.0])
    target = jnp.array([0.5, 0.5, 0.5])
    res = jax.tree_util.tree_map(jnp.zeros_like, {"w": w})
    params = {"w": w}
    for step in range(400):
        g = jax.tree_util.tree_map(lambda p, t: 2 * (p - t), params,
                                   {"w": target})
        c, res = tree_quantize(g, res)
        g_hat = tree_dequantize(c)
        params = jax.tree_util.tree_map(lambda p, gg: p - 0.05 * gg,
                                        params, g_hat)
    np.testing.assert_allclose(params["w"], target, atol=1e-2)


def test_compression_ratio():
    grads = {"a": jnp.zeros((1000,)), "b": jnp.zeros((24, 24))}
    r = compression_ratio(grads)
    assert 0.25 <= r < 0.26


DP_REDUCE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim.compression import quantize, compressed_psum, dequantize

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
G = jax.random.normal(jax.random.PRNGKey(0), (8, 512))   # per-worker grads

def reduce_fn(g):
    c, _ = quantize(g[0])
    val, _ = compressed_psum(c, "data")
    return val[None] / 8.0

fn = jax.shard_map(reduce_fn, mesh=mesh, in_specs=(P("data", None),),
                   out_specs=P("data", None), check_vma=False)
out = jax.jit(fn)(G)
true = jnp.mean(G, axis=0)
err = float(jnp.max(jnp.abs(out[0] - true)))
tol = float(jnp.max(jnp.abs(G))) / 127.0
assert err <= tol, (err, tol)
print("DPREDUCE-OK", err)
"""


def test_compressed_dp_reduction_shard_map():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", DP_REDUCE], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DPREDUCE-OK" in r.stdout
