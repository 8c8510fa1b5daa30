"""Ring attention == ref oracle, on a real multi-device mesh (subprocess)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")

_PROG = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.kernels import ref
from repro.kernels.ring_attention import ring_attention
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
for (B, S, H, Hkv, D) in [(2, 64, 4, 2, 32), (4, 128, 14, 2, 16), (2, 64, 4, 4, 64)]:
    q = jnp.asarray(rng.standard_normal((B, S, H, D), np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D), np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D), np.float32))
    with mesh:
        out = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=mesh))(q, k, v)
    want = ref.attention(q, k, v, causal=True)
    err = float(jnp.abs(out - want).max())
    print(f"B{B} S{S} H{H}/{Hkv}: err={err:.2e}")
    assert err < 2e-5, err

# gradient flows through the ring (fori_loop -> scan, ppermute transpose)
B, S, H, D = 2, 64, 4, 32
q = jnp.asarray(rng.standard_normal((B, S, H, D), np.float32))
k = jnp.asarray(rng.standard_normal((B, S, H, D), np.float32))
v = jnp.asarray(rng.standard_normal((B, S, H, D), np.float32))
def loss_ring(q, k, v):
    return jnp.sum(ring_attention(q, k, v, mesh=mesh) ** 2)
def loss_ref(q, k, v):
    return jnp.sum(ref.attention(q, k, v, causal=True).astype(jnp.float32) ** 2)
with mesh:
    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
for a, b in zip(g1, g2):
    err = float(jnp.abs(a - b).max())
    print("grad err", err)
    assert err < 5e-4, err
print("RING OK")
"""


@pytest.mark.slow
def test_ring_attention_subprocess():
    env = {**os.environ, "PYTHONPATH": SRC}
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _PROG], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "RING OK" in r.stdout
