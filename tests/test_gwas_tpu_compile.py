"""The two ``hbam vcf-gwas`` kernels and the whole load step, compiled for a
TPU v5e that is described and not attached, at the cell's real sizes: what
Mosaic refuses (a slice off the tiling, too much fast memory, an op it
cannot lower) costs no chip time.  Nothing runs, so nothing here says a word
about results or speed.  One file, and the topology only inside a fixture:
see the ``on-chip-measurement`` guide, section 2.
"""
from __future__ import annotations

import os

import pytest

CAP, SP, S, P = 268_288, 2_560, 2_504, 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding):
    import jax

    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def test_the_grm_kernel_compiles_for_the_chip(one_chip):
    import jax
    import jax.numpy as jnp

    from hadoop_bam_tpu.ops import gwas_pallas as gp

    sd = _shape(one_chip)
    step = jax.jit(lambda *a: gp.grm_accumulate(*a, interpret=False),
                   donate_argnums=(0,))
    compiled = step.lower(sd((SP, SP), jnp.float32), sd((3584, SP), jnp.int8),
                          sd((3584,), jnp.float32),
                          sd((3584,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "hbam_grm_kernel" in text
    # the accumulator is updated in place
    assert compiled.memory_analysis().alias_size_in_bytes == 4 * SP * SP


@pytest.mark.parametrize("with_table", [False, True])
def test_the_assoc_kernel_compiles_for_the_chip(one_chip, with_table):
    import jax
    import jax.numpy as jnp

    from hadoop_bam_tpu.ops import gwas_pallas as gp

    sd = _shape(one_chip)
    step = jax.jit(lambda *a: gp.assoc_scan(
        *a, n_traits=P, n_cov=5, n_samples=S, with_table=with_table,
        interpret=False))
    compiled = step.lower(sd((CAP, SP), jnp.int8),
                          sd((3, SP, 384), jnp.bfloat16),
                          sd((384,), jnp.float32),
                          sd((1,), jnp.int32)).compile()
    assert "hbam_assoc_kernel" in compiled.as_text()
    out = compiled.memory_analysis().output_size_in_bytes
    # a few KB leave the kernel, and the [cap, P] table only when asked for
    assert (out > 4 * CAP * P) == with_table and out < 4 * CAP * P + (1 << 20)
