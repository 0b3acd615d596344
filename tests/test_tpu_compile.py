"""Compile the served path's kernels and IVF steps for a described (not
attached) TPU v5e at served widths: MS MARCO v1 passage shapes, 32-token
queries, 1000 candidates, a 1M-passage IVF index. Nothing runs; the TPU
compiler refuses here what the chip would refuse (tile alignment, VMEM,
unsupported vector shapes or casts, device memory).

The topology is described inside a fixture, never while a module imports:
only one process may load the TPU library, and test workers must all
collect the same tests."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.roofline.analysis import device_peaks

LQ, K, T, D_BOW, D_CLS = 32, 1000, 180, 32, 128
NCELLS, MAX_CELL, BATCH, NPROBE, PROBE_CHUNK = 3703, 810, 12, 128, 64
FDE_DOCS, FDE_DIM = 100_000, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip. A compile for it would be written to the
    persistent cache and never read back, so the cache is off meanwhile."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def hbm_bytes(topo):
    return device_peaks(topo.devices[0].device_kind)["hbm_bytes"]


def _sds(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _lowered(name, chip, dtype):
    s = lambda shape, dt: _sds(chip, shape, dt)
    if name == "maxsim":
        from repro.kernels.maxsim.maxsim import maxsim_pallas
        return maxsim_pallas.lower(
            s((LQ, D_BOW), dtype), s((LQ,), jnp.float32),
            s((K, T, D_BOW), dtype), s((K,), jnp.int32), interpret=False)
    if name == "bitsim":
        from repro.kernels.bitsim.bitsim import bitsim_pallas
        return bitsim_pallas.lower(
            s((LQ, D_BOW), dtype), s((LQ,), jnp.float32),
            s((K, T, -(-D_BOW // 32)), jnp.uint32), s((K,), jnp.int32),
            d=D_BOW, interpret=False)
    if name == "fdescan":
        from repro.kernels.fdescan.fdescan import fdescan_pallas
        return fdescan_pallas.lower(s((BATCH, FDE_DIM), jnp.float32),
                                    s((FDE_DOCS, FDE_DIM), dtype),
                                    interpret=False)
    from repro.kernels.ivf_scan.ivf_scan import ivf_scan_pallas
    return ivf_scan_pallas.lower(s((BATCH, D_CLS), dtype),
                                 s((NCELLS, D_CLS), dtype), interpret=False)


@pytest.mark.parametrize("name,dtype", [
    ("maxsim", jnp.float32), ("maxsim", jnp.bfloat16),
    ("bitsim", jnp.float32), ("fdescan", jnp.float16),
    ("fdescan", jnp.float32), ("ivf_scan", jnp.float32)])
def test_kernel_compiles_for_v5e(chip, name, dtype):
    compiled = _lowered(name, chip, dtype).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fdescan_fp16_upcast_fused_into_kernel(chip):
    """The fp16 table's pad + fp32 upcast is fused into the kernel operand:
    the entry computation holds no fp32 copy of the table and the program
    needs no temporary buffer of that size."""
    compiled = _lowered("fdescan", chip, jnp.float16).compile()
    rows = -(-FDE_DOCS // 256) * 256
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    assert "tpu_custom_call" in text
    assert f"f32[{rows},{FDE_DIM}]" not in entry
    assert f"f16[{FDE_DOCS},{FDE_DIM}]" in entry
    assert compiled.memory_analysis().temp_size_in_bytes < rows * FDE_DIM * 4


def test_ivf_probe_cells_compiles_for_v5e(chip, hbm_bytes):
    from repro.core.ivf import probe_cells
    compiled = probe_cells.lower(_sds(chip, (NCELLS, D_CLS), jnp.float32),
                                 _sds(chip, (BATCH, D_CLS), jnp.float32),
                                 nprobe=NPROBE).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < hbm_bytes


@pytest.mark.parametrize("quant", ["fp32", "int8"])
def test_ivf_scan_block_compiles_for_v5e(chip, hbm_bytes, quant):
    from repro.core.ivf import _scan_block
    vec_dtype = jnp.int8 if quant == "int8" else jnp.float32
    scale = (_sds(chip, (NCELLS, MAX_CELL), jnp.float32)
             if quant == "int8" else None)
    compiled = _scan_block.lower(
        _sds(chip, (NCELLS, MAX_CELL), jnp.int32),
        _sds(chip, (NCELLS, MAX_CELL, D_CLS), vec_dtype), scale,
        _sds(chip, (BATCH, D_CLS), jnp.float32),
        _sds(chip, (BATCH, PROBE_CHUNK), jnp.int32), k=K).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < hbm_bytes
