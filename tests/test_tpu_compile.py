"""The main-path kernels compile for a TPU v5e at the on-chip smoke sizes.

No chip is needed: the topology of a v5e 2x2 host is described, and each
program is lowered with shapes placed on its devices and compiled by the
TPU compiler, which refuses what the chip would refuse (SMEM or VMEM
overflow, unaligned tiles, programs larger than device memory).  Sizes
follow ``chip_smoke.py``: bs = 128 and the pair, operand and output-slot
counts of its ``multiply`` phase (n = 32,768), the ``scf`` phase's leaf
size, and the ``mesh`` phase's per-device wave on a four-device mesh.

The topology is described only inside the fixture, never at import:
one process may load the TPU library at a time.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.kernels import tri
from repro.kernels.batched_gemm import batched_gemm
from repro.kernels.bsmm_pairs import MAX_PAIRS, bsmm_pairs
from repro.launch.mesh_exec import mesh_wave

BS = 128
HBM_BYTES = 16 * 2 ** 30       # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("pairs,cap_ab,cap_c", [
    (2_624, 600, 900),          # one call (pairs <= MAX_PAIRS)
    (95_000, 5_000, 36_000),    # the multiply phase's general product
])
def test_bsmm_pairs_compiles(one_chip, pairs, cap_ab, cap_c):
    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = bsmm_pairs.lower(
        s((cap_ab, BS, BS)), s((cap_ab, BS, BS)), s((pairs,), jnp.int32),
        s((pairs,), jnp.int32), s((pairs,), jnp.int32),
        cap_c=cap_c).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert (pairs > MAX_PAIRS) == ("while" in text)   # the call loop
    assert _device_bytes(compiled) < HBM_BYTES


def test_batched_gemm_compiles(one_chip):
    x = jax.ShapeDtypeStruct((4_096, BS, BS), jnp.float32, sharding=one_chip)
    compiled = batched_gemm.lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("op", ["inv_chol", "tri_solve"])
def test_triangular_waves_compile(one_chip, op):
    # the scf phase: n = 4,096 at leaf_n = 512 -> up to 8 leaves per wave;
    # XLA-native Cholesky/triangular solve, no Pallas kernel expected
    x = jax.ShapeDtypeStruct((8, 512, 512), jnp.float32, sharding=one_chip)
    if op == "inv_chol":
        compiled = jax.jit(tri.batched_inv_chol).lower(x).compile()
    else:
        compiled = jax.jit(tri.batched_tri_solve).lower(x, x).compile()
    assert _device_bytes(compiled) < HBM_BYTES


#: per-device mesh wave of the ``mesh`` phase (n = 65,536 over four v5e
#: chips), from its chip reading: 107,992 pairs (``cap_p``) and 52,575
#: output slots (``cap_c``) on the busiest device, 10,747 home blocks on
#: the busiest device; about 9,000 received blocks over three ring shifts
_MESH_CAPS = dict(cap_own=10_747, cap_p=107_992, cap_c=52_575,
                  cnts=(4_000, 1_000, 4_000))


def _lower_mesh_wave(topo, cap_own, cap_p, cap_c, cnts):
    n_dev = 4
    mesh = Mesh(np.asarray(topo.devices[:n_dev]), ("dev",))
    on_mesh = NamedSharding(mesh, P("dev"))

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct((n_dev, *shape), dt, sharding=on_mesh)
    pool = s((cap_own, BS, BS))
    idx = s((cap_p,), jnp.int32)
    sels = tuple(s((c,), jnp.int32) for c in cnts)
    return mesh_wave.lower(
        pool, idx, idx, idx, sels, mesh=mesh,
        shifts=(1, 2, 3)[:len(cnts)], cap_c=cap_c,
        use_pallas=True, interpret=False)


def test_mesh_wave_compiles(topo):
    compiled = _lower_mesh_wave(topo, **_MESH_CAPS).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
    assert _device_bytes(compiled) < HBM_BYTES
