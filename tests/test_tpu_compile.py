"""Compiles for a described TPU v5e, with no chip attached: the five Pallas
kernels at real widths, and the serve steps of phi3-mini-3.8b and
minicpm3-4b at full depth.

Nothing runs.  A compile the TPU compiler refuses — an unsupported
lowering, a kernel over its fast memory, a program over the chip's HBM —
fails here at no chip time.  The topology is described inside a fixture:
only the process that runs this file loads the TPU library, and a module
that loads it while being imported would give the test workers different
tests to collect."""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import RunConfig
from repro.configs import get_config
from repro.kernels import (flash_attention, moe_gemm, queue_matmul,
                           rglru_scan, ssm_scan)
from repro.models.model import cache_spec, decode_step, param_shapes, \
    prefill_step

#: the serve size chip_smoke.py runs for phi3-mini-3.8b
SLOTS, MAX_LEN, CHUNK = 4, 1024, 8
#: minicpm3-4b's slots in the benchmark's ``minicpm3.chat`` cell
MINICPM3_SLOTS = 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


BF16, F32 = jnp.bfloat16, jnp.float32
KERNELS = {
    "queue_matmul": (partial(queue_matmul, interpret=False),
                     [((4096, 3072), BF16), ((3072, 8192), BF16)]),
    "flash_attention": (partial(flash_attention, interpret=False),
                        [((1, 32, 2048, 96), BF16)] * 3),
    "moe_gemm": (partial(moe_gemm, interpret=False),
                 [((8, 512, 2048), BF16), ((8, 2048, 1024), BF16)]),
    "ssm_scan": (partial(ssm_scan, interpret=False),
                 [((1, 2048, 8192), F32), ((1, 2048, 8192), F32),
                  ((8192, 16), F32), ((1, 2048, 16), F32),
                  ((1, 2048, 16), F32)]),
    "rglru_scan": (partial(rglru_scan, interpret=False),
                   [((1, 2048, 2560), F32)] * 2),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in args]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _serve_step(sharding, arch, slots, step):
    """``step`` of ``arch`` at published widths and full depth, bf16, as the
    engine runs it with ``slots`` x ``MAX_LEN``, compiled for one described
    chip: the compiler refuses a program over the chip's HBM."""
    cfg = get_config(arch)
    rc = RunConfig(dtype="bfloat16", param_dtype="bfloat16", remat=False)
    params = _on(sharding, param_shapes(cfg, BF16))
    cache = _on(sharding, cache_spec(cfg, slots, MAX_LEN, BF16))
    width = 1 if step == "decode_step" else CHUNK
    batch = {"tokens": jax.ShapeDtypeStruct((slots, width), jnp.int32)}
    if step == "prefill_step":
        batch["n_tokens"] = jax.ShapeDtypeStruct((slots,), jnp.int32)
    fn = {"decode_step": decode_step, "prefill_step": prefill_step}[step]
    compiled = jax.jit(partial(fn, cfg=cfg, rc=rc)).lower(
        params, cache, _on(sharding, batch)).compile()
    held = sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves((params, cache)))
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= held
    return memory


@pytest.mark.parametrize("step", ["decode_step", "prefill_step"])
def test_phi3_serve_step_compiles_for_v5e(one_chip, step):
    """phi3-mini-3.8b at 4 x 1024.  ``prefill_step`` is one pass over the
    chunk, so its temporaries stay under two 1.61 GB caches (0 bytes with
    libtpu 0.0.34; a column scan of decode_step needs 6.44 GB)."""
    memory = _serve_step(one_chip, "phi3-mini-3.8b", SLOTS, step)
    if step == "prefill_step":
        assert memory.temp_size_in_bytes < 3.2e9


@pytest.mark.parametrize("step", ["decode_step", "prefill_step"])
def test_minicpm3_serve_step_compiles_for_v5e(one_chip, step):
    """minicpm3-4b (MLA) at the benchmark's 16 x 1024."""
    _serve_step(one_chip, "minicpm3-4b", MINICPM3_SLOTS, step)
