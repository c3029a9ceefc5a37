"""Gradient-compression correctness (needs 8 fake devices → subprocess,
because the main pytest process must keep the real 1-device platform)."""

import os
import subprocess
import sys

import numpy as np

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.parallel.compression import compressed_psum_mean

mesh = jax.make_mesh((8,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 1000))
err = jnp.zeros((8, 1000))

@jax.jit
def run(g, err):
    f = jax.shard_map(lambda gl, el: compressed_psum_mean(gl[0], el[0], "data"),
                      mesh=mesh, in_specs=(P("data", None), P("data", None)),
                      out_specs=(P(None), P("data")), check_vma=False)
    return f(g, err)

mean, new_err = run(g, err)
true = g.mean(axis=0)
rel = float(jnp.abs(mean - true).max() / jnp.abs(true).max())
assert rel < 0.05, rel
# error feedback: residual equals what quantization dropped
assert float(jnp.abs(new_err).max()) > 0
print("REL", rel)
"""


def test_compressed_allreduce_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rel = float(out.stdout.strip().split()[-1])
    assert rel < 0.05
