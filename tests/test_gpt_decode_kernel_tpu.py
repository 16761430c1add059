"""The paged-attention kernel compiled by the TPU's own compiler, for a
chip that is described and not attached (v5e), at the shapes the chip
runs: what interpret mode cannot see — a slice off the tiling, a DMA
Mosaic cannot address, more VMEM than a kernel may take — fails here,
at no chip time. Nothing runs: a compile that passes is not a chip run.

The topology is described inside a fixture (only the worker that is
given this file loads the TPU's library), and every such test lives in
this ONE file."""
import os

import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_mode(monkeypatch):
    """The kernel as a TPU process builds it: the platform here is the
    CPU, so THE one decision is steered in the test."""
    from ray_tpu._private import chip

    monkeypatch.setattr(chip, "pallas_interpret", lambda: False)


#: (B, H, hd, page_size, n_pages, max_pages, kv_dtype)
SHAPES = {
    # both serving cells: 24 layers x 2048 pages, flattened
    "serving-fp": (32, 16, 128, 16, 24 * 2048, 128, "fp"),
    "serving-int8": (32, 16, 128, 16, 24 * 2048, 128, "int8"),
    # tp=4 on the same model: four heads a device
    "tp4-fp": (32, 4, 128, 16, 24 * 2048, 128, "fp"),
    "tp4-int8": (32, 4, 128, 16, 24 * 2048, 128, "int8"),
    # chip_smoke's tp probe: one page a lane, pt[s] = [s]
    "tp-probe": (2, 4, 128, 32, 2, 1, "fp"),
    # a page that is a lane's whole max_len: the chunked loop, one slot
    "max-len-page": (4, 16, 128, 2048, 8, 1, "fp"),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_paged_attention_compiles_for_v5e(one_chip, compiled_mode, shape):
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import chip
    from ray_tpu.models import gpt_decode as gd

    B, H, hd, ps, n_pages, max_pages, kv_dtype = SHAPES[shape]
    quant = kv_dtype == "int8"

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((n_pages, ps, H, hd), jnp.int8 if quant else jnp.bfloat16)
    args = [arg((B, 1, H, hd), jnp.bfloat16), pool, pool,
            arg((B, max_pages), jnp.int32), arg((B,), jnp.int32)]
    if quant:
        args += [arg((n_pages, H), jnp.float32)] * 2
    lowered = jax.jit(
        lambda q, kc, vc, pt, pos, ks=None, vs=None: gd.paged_attention(
            q, kc, vc, pt, pos, page_size=ps, kernel="pallas", ks=ks,
            vs=vs)).lower(*args)
    assert chip.compiled_by_mosaic(lowered.as_text())
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    # the pool is an operand of the kernel as it lies: no copy of it
    assert f"[{n_pages},{ps},{H},{hd}]" in text
    assert "paged_attention/pallas_call" in text     # the scope's path


def test_heads_off_the_tiling_are_refused_by_name(compiled_mode):
    """Mosaic addresses a page in whole tiles: a model whose heads are
    not (GPT-2 small: 12 heads of 64) is refused when the program is
    built, with the shapes and the way out, not deep in a compile."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode as gd

    q = jnp.zeros((2, 1, 12, 64), jnp.bfloat16)
    pool = jnp.zeros((8, 16, 12, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="H=12, hd=64.*gather"):
        gd.paged_attention(q, pool, pool, jnp.zeros((2, 4), jnp.int32),
                           jnp.zeros((2,), jnp.int32), page_size=16,
                           kernel="pallas")
