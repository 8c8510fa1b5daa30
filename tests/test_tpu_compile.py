"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, unsigned reductions, programs larger than HBM.  These tests
compile the checkpoint fingerprint kernels at the delta plane's real chunk
size and the full-width qwen2-0.5b train step, so such a refusal fails here
and not on the chip.  The topology is described inside a fixture: only the
worker that runs this file loads the TPU library.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding  # noqa: E402

from repro.checkpoint.serialization import DELTA_CHUNK_BYTES  # noqa: E402
from repro.kernels import checksum as ck  # noqa: E402

CHUNK_WORDS = DELTA_CHUNK_BYTES // 4
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n", [CHUNK_WORDS * 8, CHUNK_WORDS * 8 + 1000],
                         ids=["aligned", "ragged"])
def test_chunk_fingerprints_kernel_compiles(one_chip, n):
    words = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip)
    compiled = ck.chunk_fingerprints_pallas.lower(
        words, chunk_words=CHUNK_WORDS).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [2048 * 64, 5000])
def test_checksum_kernel_compiles(one_chip, n):
    words = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip)
    compiled = ck.checksum_pallas.lower(words).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_train_step_fits_one_chip(topo):
    """launch/train.py's step (qwen2-0.5b at published widths, batch 8,
    seq 128, no donation) on one v5e: state in, state out and temporaries
    fit its 16 GB of HBM."""
    from repro.configs.base import get_config
    from repro.optim import adamw
    from repro.parallel.mesh_rules import Rules
    from repro.train import step as TS

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("qwen2-0.5b")
    oc = adamw.OptConfig(warmup_steps=10, decay_steps=100)
    rules = Rules(mesh)
    jitted, st_sh, batch_sh_fn = TS.make_train_step(
        cfg, mesh, oc, rules=rules, donate=False)
    state = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        TS.abstract_train_state(cfg, oc), st_sh)
    tokens = jax.ShapeDtypeStruct(
        (8, 128), jnp.int32, sharding=NamedSharding(mesh, jax.P()))
    mem = jitted.lower(state, {"tokens": tokens}).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem
