"""The decode step's kernels (``gpt_decode``'s attention over pages of
keys and values per head, ``mla_moe``'s over latent pages (and again
under ``dsa_moe``'s selection, masked), ``kda_moe``'s
recurrence on the per-slot state and its grouped-query attention over
pages of ``(token, KV head)`` rows, ``ssm_hybrid``'s recurrence on its
state-space state, and the last two again as ``ssm_moe`` imports them)
compiled by the
TPU's own compiler, for a chip that is described and not attached
(v5e), at the shapes the chip runs: what interpret mode cannot see —
a slice off the tiling, a DMA Mosaic cannot address, more VMEM than a
kernel may take — fails here, at no chip time. Nothing runs: a compile
that passes is not a chip run.

Since PR 58 also the fsdp TRAINING step, whole, for the four
described chips: the plan the TPU's compiler makes of it (which sinks
a loop-invariant gather into the scan where the CPU's hoists it), read
with ``parallel.sharding.compiled_collectives``.

The topology is described inside a fixture (only the worker that is
given this file loads the TPU's library), and every such test lives in
this ONE file."""
import os

import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

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
    # a page that is a block by itself (128 tokens x 16 heads), one slot
    "block-page": (4, 16, 128, 128, 64, 16, "fp"),
    # a page that is a lane's whole max_len: sixteen blocks a page, each
    # a part of it and a copy, the same ring
    "max-len-page": (4, 16, 128, 2048, 8, 1, "fp"),
    "max-len-page-int8": (4, 16, 128, 2048, 8, 1, "int8"),
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
    # the scope's path, the kernel's own name inside it
    assert "paged_attention/gqa_attention/pallas_call" in text


#: (H, hd, page_size, pool dtype) -> what the message must name
REFUSED = {
    # GPT-2 small: 12 heads of 64, half a lane tile a head
    "heads-of-64": (12, 64, 16, "bfloat16", "H=12, hd=64"),
    # 8 x 3 = 24 rows a page: a tile and a half of bfloat16
    "rows-off-the-tile": (3, 128, 8, "bfloat16", "H=3, hd=128"),
    # int8 codes lie 32 rows a tile: a page of 16 x 1 is half of one
    "int8-rows-off-the-tile": (1, 128, 16, "int8", "page_size=16, int8"),
    # a page of 400 rows, 25 tiles, is read in two parts of 200: twelve
    # tiles and a half
    "part-off-the-tile": (1, 128, 400, "bfloat16", "page_size=400"),
}


@pytest.mark.parametrize("shape", sorted(REFUSED))
def test_heads_off_the_tiling_are_refused_by_name(compiled_mode, shape):
    """Mosaic addresses a page's rows, ``(token, head)`` as they lie, in
    whole tiles: a pool whose pages (or, of a page read in parts, whose
    parts) are not whole tiles (GPT-2 small: 12 heads of 64) is refused
    when the program is built, with the shapes and the way out, not
    deep in a compile."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode as gd

    H, hd, ps, dtype, named = REFUSED[shape]
    quant = dtype == "int8"
    q = jnp.zeros((2, 1, H, hd), jnp.bfloat16)
    pool = jnp.zeros((8, ps, H, hd), dtype)
    scale = jnp.ones((8, H), jnp.float32) if quant else None
    with pytest.raises(ValueError, match=f"{named}.*gather"):
        gd.paged_attention(q, pool, pool, jnp.zeros((2, 4), jnp.int32),
                           jnp.zeros((2,), jnp.int32), page_size=ps,
                           kernel="pallas", ks=scale, vs=scale)


# ---------------------------------------------- latent attention (S5e)
def _latent_cfg():
    """``mla_moe`` at the served attention's widths (64 heads over rows
    of 512 + 64 values in 640 lanes) around a small everything else:
    what the kernel's shapes depend on."""
    import dataclasses

    from ray_tpu.models import mla_moe

    return dataclasses.replace(
        mla_moe.CONFIGS["nano"], n_layer=2, d_model=256, n_head=64,
        q_rank=128, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
        experts_held=8)


#: (B, page_size, pages in the flat pool, max_pages)
LATENT_SHAPES = {
    # the cell: 128 lanes, 7 layers x 18,432 pages flat, max_len 2,048
    "cell": (128, 16, 7 * 18432, 128),
    # the benchmark's reference check: 96 rows on a pool of their own
    "check-5": (96, 16, 7 * 96 * 5, 5),
    "check-16": (96, 16, 7 * 96 * 16, 16),
    # the shortcut model's cell (``scmoe``: the same kernel, imported):
    # 8 attentions x 12,288 pages flat
    "scmoe-cell": (128, 16, 8 * 12288, 128),
}


@pytest.mark.parametrize("shape", sorted(LATENT_SHAPES))
def test_latent_attention_compiles_for_v5e(one_chip, compiled_mode, shape):
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import chip
    from ray_tpu.models import mla_moe

    cfg = _latent_cfg()
    B, ps, n_pages, max_pages = LATENT_SHAPES[shape]
    assert cfg.latent_row == 640
    assert mla_moe.decode_attention_fused(cfg, ps)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(
        lambda q, pool, pages, length: mla_moe._latent_attention_pallas(
            q, pool, pages, length, cfg, ps)).lower(
        arg((B, cfg.n_head, 640), jnp.bfloat16),
        arg((n_pages, ps, 640), jnp.bfloat16),
        arg((B, max_pages), jnp.int32), arg((B,), jnp.int32))
    assert chip.compiled_by_mosaic(lowered.as_text())
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    # the pool is an operand of the kernel as it lies: no copy of it
    assert f"[{n_pages},{ps},640]" in text
    assert "latent_attention/pallas_call" in text


@pytest.mark.parametrize("ps,fused", [(16, True), (4, False)])
def test_the_latent_step_chooses_by_the_page_for_v5e(one_chip,
                                                    compiled_mode, ps,
                                                    fused):
    """The whole decode step as a TPU process builds it: with pages of
    16 rows the kernel, under the path the benchmark's readers look
    for; with pages of 4 (a quarter of a bfloat16 tile, which Mosaic's
    DMA cannot address) the XLA body, and nothing raises."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu._private import chip
    from ray_tpu.models import mla_moe

    cfg = _latent_cfg()
    B, n_pages, max_pages = 8, 64, 64 // ps

    def arg(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda k: mla_moe.init_params(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: mla_moe.init_paged_cache(cfg, B, n_pages, ps))
    args = (params, cache, jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((B, max_pages), jnp.int32))
    assert mla_moe.decode_attention_fused(cfg, ps) is fused
    lowered = jax.jit(functools.partial(
        mla_moe._slot_decode_step_paged, cfg=cfg, page_size=ps),
        donate_argnums=(1,)).lower(*jax.tree.map(arg, args))
    assert chip.compiled_by_mosaic(lowered.as_text()) is fused
    text = lowered.compile().as_text()
    assert ("decode_step/mla.attention/latent_attention/pallas_call"
            in text) is fused
    assert ("tpu_custom_call" in text) is fused


def test_the_shortcut_models_step_holds_the_imported_kernel_for_v5e(
        one_chip, compiled_mode):
    """``scmoe``'s decode step as a TPU process builds it: the latent
    attention is ``mla_moe``'s kernel, once an ATTENTION (two a layer),
    under the path the benchmark's readers look for, over ONE pool that
    counts both attentions of every layer."""
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu._private import chip
    from ray_tpu.models import scmoe

    cfg = dataclasses.replace(
        scmoe.CONFIGS["nano"], n_layer=2, d_model=256, n_head=64,
        q_rank=128, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
        experts_held=8)
    B, ps, n_pages = 8, 16, 64

    def arg(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda k: scmoe.init_params(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: scmoe.init_paged_cache(cfg, B, n_pages, ps))
    assert cache["latent"].shape == (4, n_pages, ps, 640)
    args = (params, cache, jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((B, n_pages // ps), jnp.int32))
    assert scmoe.decode_attention_fused(cfg, ps)
    lowered = jax.jit(functools.partial(
        scmoe._slot_decode_step_paged, cfg=cfg, page_size=ps),
        donate_argnums=(1,)).lower(*jax.tree.map(arg, args))
    assert chip.compiled_by_mosaic(lowered.as_text())
    text = lowered.compile().as_text()
    assert "decode_step/mla.attention/latent_attention/pallas_call" in text
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 4
    for scope in ("scmoe.dense", "moe.route", "moe.experts", "moe.zero"):
        assert f"decode_step/{scope}/" in text, scope


def test_the_sparse_models_step_masks_the_imported_kernel_for_v5e(
        one_chip, compiled_mode):
    """``dsa_moe``'s decode step as a TPU process builds it, at the
    cell's attention and indexer widths and its ``max_len`` (128 heads,
    64 index heads of 128, pages of 16 to 5,120 tokens, ``index_topk``
    2,048): the latent attention is ``mla_moe``'s kernel with the
    selection's mask as one more operand (a float32 row a lane in VMEM,
    sliced a block at a time: the slice is on the tiling), once a
    layer, under the selection's own scope; the bisection for the
    2,048th score is a kernel of its own under ``dsa.select``
    (``pick_top``: 64 lanes' scores a grid step in VMEM), once a
    layer too; the index scores are plain XLA under theirs."""
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu._private import chip
    from ray_tpu.models import dsa_moe

    cfg = dataclasses.replace(
        dsa_moe.CONFIGS["nano"], n_layer=2, d_model=256, n_head=128,
        q_rank=128, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
        experts_held=8, index_heads=64, index_dim=128, index_topk=2048)
    B, ps, max_pages = 8, 16, 320
    n_pages = B * max_pages

    def arg(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda k: dsa_moe.init_params(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: dsa_moe.init_paged_cache(cfg, B, n_pages, ps))
    assert cache["latent"].shape == (2, n_pages, ps, 640)
    assert cache["ikey"].shape == (2, n_pages, ps, 128)
    args = (params, cache, jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((B, max_pages), jnp.int32))
    assert dsa_moe.decode_attention_fused(cfg, ps)
    lowered = jax.jit(functools.partial(
        dsa_moe._slot_decode_step_paged, cfg=cfg, page_size=ps),
        donate_argnums=(1,)).lower(*jax.tree.map(arg, args))
    assert chip.compiled_by_mosaic(lowered.as_text())
    text = lowered.compile().as_text()
    assert "decode_step/dsa.attention/latent_attention/pallas_call" in text
    assert "decode_step/dsa.select/pick_top/pallas_call" in text
    assert "mla.attention" not in text
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 4
    for scope in ("dsa.index", "moe.route", "moe.experts",
                  "moe.shared"):
        assert f"decode_step/{scope}/" in text, scope


# ------------------------------- the gated delta-rule recurrence (S5f)
#: (n_kda, lanes): the cell's per-slot entry, and the benchmark's
#: reference check (48 rows on a pool of their own)
KDA_SHAPES = {"cell": (3, 256), "check": (3, 48)}


@pytest.mark.parametrize("shape", sorted(KDA_SHAPES))
def test_kda_state_kernel_compiles_for_v5e(one_chip, compiled_mode, shape):
    """The recurrence's kernel at the served widths (64 heads of a
    [128, 128] float32 state): Mosaic takes a head's columns as one
    lane of the transposed operand, the whole per-slot entry is the
    kernel's operand AND result (aliased: no copy of it, whichever
    layer is named), one ``tpu_custom_call`` a layer."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import chip
    from ray_tpu.models import kda_moe

    L, B = KDA_SHAPES[shape]
    H, D = 64, 128

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(state, q, k, v, g, beta, active):
        for layer in range(L):
            state, o = kda_moe._kda_step_pallas(state, layer, q, k, v, g,
                                                beta, active)
            q = q + o
        return state, q

    lowered = jax.jit(step, donate_argnums=(0,)).lower(
        arg((L, B, H, D, D)), arg((B, H, D)), arg((B, H, D)),
        arg((B, H, D)), arg((B, H, D)), arg((B, H)),
        arg((B,), jnp.bool_))
    assert chip.compiled_by_mosaic(lowered.as_text())
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == L
    assert "kda_state/pallas_call" in text
    state_bytes = L * B * H * D * D * 4
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == state_bytes
    assert memory.temp_size_in_bytes < state_bytes // 16


def _kda_step_lowered(one_chip, **widths):
    """``kda_moe``'s whole decode step as a TPU process builds it, at
    ``nano`` around the given widths: ``(cfg, page_size, lowered)``."""
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import kda_moe

    cfg = dataclasses.replace(kda_moe.CONFIGS["nano"], d_model=256,
                              kda_heads=16, experts_held=8, **widths)
    B, ps, n_pages = 8, 16, 32

    def arg(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda k: kda_moe.init_params(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: kda_moe.init_paged_cache(cfg, B, n_pages, ps))
    args = (params, cache, jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((B, n_pages // B), jnp.int32))
    return cfg, ps, jax.jit(functools.partial(
        kda_moe._slot_decode_step_paged, cfg=cfg, page_size=ps),
        donate_argnums=(1,)).lower(*jax.tree.map(arg, args))


@pytest.mark.parametrize("kda_hd,fused", [(128, True), (64, False)])
def test_the_kda_step_chooses_by_the_head_for_v5e(one_chip, compiled_mode,
                                                  kda_hd, fused):
    """The whole decode step as a TPU process builds it: with heads of
    128 the recurrence's kernel, under the path the benchmark's readers
    look for (``decode_step/kda.state``); with heads of 64 (half a lane
    tile) ``_kda_step``, and nothing raises. (The GQA layer's heads are
    of 64 in both: no kernel there, so the program holds one or none.)"""
    from ray_tpu._private import chip
    from ray_tpu.models import kda_moe

    cfg, ps, lowered = _kda_step_lowered(one_chip, kda_head_dim=kda_hd,
                                         head_dim=64)
    assert kda_moe._state_kernel(cfg) is fused
    assert kda_moe.decode_attention_fused(cfg, ps) is fused
    assert chip.compiled_by_mosaic(lowered.as_text()) is fused
    text = lowered.compile().as_text()
    assert ("decode_step/kda.state/kda_state/pallas_call" in text) is fused
    assert "gqa_attention/pallas_call" not in text


# ------------------------------------- grouped-query attention (S5g)
#: (lanes, pages in the flat pool, max_pages): the cell (one GQA layer
#: of 20,480 pages, 256 lanes of 128 pages) and the benchmark's
#: reference check (48 rows on a pool of their own)
#: the linear-attention model's cell and check (64 / 8 heads), then the
#: parallel hybrid's (``models/ssm_hybrid.py``: 20 / 4 heads, five
#: layers' pools flat, through the PUBLIC entry)
GQA_SHAPES = {"cell": (256, 20480, 128, 64, 8),
              "check": (48, 48 * 16, 16, 64, 8),
              "hybrid-cell": (128, 5 * 10240, 128, 20, 4),
              "hybrid-check": (8, 5 * 8 * 5, 5, 20, 4)}


@pytest.mark.parametrize("shape", sorted(GQA_SHAPES))
def test_gqa_attention_compiles_for_v5e(one_chip, compiled_mode, shape):
    """The attention's kernel at the served widths (64 query heads over
    8 KV heads of 128, and 20 over 4, pages of 16): Mosaic takes a page
    as ``16 x Hkv`` rows of ``(token, KV head)`` and a lane's 20
    queries, no whole sublane tile, as one operand; both pools are the
    kernel's operands as they lie (no copy, no transposed page), one
    ``tpu_custom_call``, ONE body for both models."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import chip
    from ray_tpu.models import kda_moe

    B, n_pages, max_pages, Hq, Hkv = GQA_SHAPES[shape]
    ps = 16
    assert kda_moe.gqa_kernel(Hkv, 128, jnp.bfloat16, ps)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((n_pages, ps, Hkv, 128))
    lowered = jax.jit(
        lambda q, k, v, pages, length: kda_moe.gqa_decode_attention(
            q, k, v, pages, None, length, n_head=Hq, n_kv_head=Hkv,
            head_dim=128, dtype=jnp.bfloat16, page_size=ps)).lower(
        arg((B, Hq, 128)), pool, pool, arg((B, max_pages), jnp.int32),
        arg((B,), jnp.int32))
    assert chip.compiled_by_mosaic(lowered.as_text())
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "gqa_attention/pallas_call" in text
    # the pools go in as they lie: no temporary the size of a gather
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


@pytest.mark.parametrize("hd,fused", [(128, True), (64, False)])
def test_the_gqa_step_chooses_by_the_page_and_head_for_v5e(
        one_chip, compiled_mode, hd, fused):
    """The whole decode step as a TPU process builds it: with heads of
    128 (pages of 16 x 2 KV heads: two bfloat16 tiles of rows) the
    kernel, under the path the benchmark's readers look for
    (``decode_step/gqa.attention``); with heads of 64 (half a lane
    tile, which Mosaic cannot address) the gather, and nothing raises.
    The recurrence's heads are of 64 in both: no kernel there."""
    from ray_tpu._private import chip
    from ray_tpu.models import kda_moe

    cfg, ps, lowered = _kda_step_lowered(one_chip, kda_head_dim=64,
                                         head_dim=hd)
    assert kda_moe._gqa_kernel(cfg, ps) is fused
    assert kda_moe.decode_attention_fused(cfg, ps) is fused
    assert chip.compiled_by_mosaic(lowered.as_text()) is fused
    text = lowered.compile().as_text()
    assert ("decode_step/gqa.attention/gqa_attention/pallas_call"
            in text) is fused
    assert ("tpu_custom_call" in text) is fused
    assert "kda_state/pallas_call" not in text


# ------------------------------- the parallel hybrid's step (ISSUE 52)
@pytest.mark.parametrize("hd,ssm_state,gqa,state", [
    (128, 256, True, True), (64, 256, False, True),
    (128, 192, True, False), (64, 192, False, False)])
def test_the_hybrid_step_chooses_by_the_page_and_head_for_v5e(
        one_chip, compiled_mode, hd, ssm_state, gqa, state):
    """``ssm_hybrid``'s whole decode step as a TPU process builds it,
    at ``nano`` around 20 query heads over 4 KV heads and 8 state heads
    of 128 channels, TWO choices by shape: with attention heads of 128
    (pages of 16 x 4 KV heads) ``kda_moe``'s kernel in EVERY layer,
    under the path the benchmark's readers look for
    (``decode_step/hgqa.attention``), with heads of 64 the gather; with
    a state 256 wide (whole 128-lane tiles) the recurrence's kernel
    (ISSUE 53) once a layer under ``decode_step/ssm.state``, the
    layer's entry aliased whole and nothing state-sized beside it, with
    192 (off the tile) ``_ssm_step``; and nothing raises."""
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu._private import chip
    from ray_tpu.models import ssm_hybrid

    cfg = dataclasses.replace(ssm_hybrid.CONFIGS["nano"], d_model=256,
                              n_head=20, n_kv_head=4, head_dim=hd,
                              ssm_heads=8, ssm_head_dim=128,
                              ssm_state=ssm_state)
    B, ps, n_pages = 8, 16, 32

    def arg(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda k: ssm_hybrid.init_params(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: ssm_hybrid.init_paged_cache(cfg, B, n_pages, ps))
    args = (params, cache, jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((B, n_pages // B), jnp.int32))
    lowered = jax.jit(functools.partial(
        ssm_hybrid._slot_decode_step_paged, cfg=cfg, page_size=ps),
        donate_argnums=(1,)).lower(*jax.tree.map(arg, args))
    assert ssm_hybrid._state_kernel(cfg) is state
    assert ssm_hybrid.decode_attention_fused(cfg, ps) is (gqa or state)
    assert chip.compiled_by_mosaic(lowered.as_text()) is (gqa or state)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert ("decode_step/hgqa.attention/gqa_attention/pallas_call"
            in text) is gqa
    assert ("decode_step/ssm.state/ssm_state/pallas_call" in text) is state
    assert text.count('custom_call_target="tpu_custom_call"') \
        == cfg.n_layer * (gqa + state)
    for scope in ("ssm.state", "ssm.proj", "hybrid.mlp", "lm.head"):
        assert f"decode_step/{scope}/" in text, scope
    layer_state = B * 8 * 128 * ssm_state * 4
    memory = compiled.memory_analysis()
    # every entry of the donated pool comes out in place
    assert memory.alias_size_in_bytes >= cfg.n_layer * layer_state
    if state:
        assert memory.temp_size_in_bytes < layer_state // 2


# ---------------------------- the Mamba-2 recurrence's kernel (ISSUE 53)
#: lanes: the cell's per-slot entries (128 slots, one array a layer) and
#: the benchmark's reference check (``served_logits`` at its 8 rows)
SSM_SHAPES = {"cell": 128, "check": 8}


@pytest.mark.parametrize("shape", sorted(SSM_SHAPES))
def test_ssm_state_kernel_compiles_for_v5e(one_chip, compiled_mode, shape):
    """The recurrence's kernel at the served widths (32 heads of a
    [128, 256] float32 state in 2 groups, five layers, one entry each):
    Mosaic takes a head's ``dt x`` as one lane of the transposed
    operand and reduces ``S C`` across lanes, every layer's whole entry
    is its kernel's operand AND result (aliased: no copy of it), one
    ``tpu_custom_call`` a layer."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import chip
    from ray_tpu.models import ssm_hybrid

    B = SSM_SHAPES[shape]
    L, H, G, P, N = 5, 32, 2, 128, 256
    assert ssm_hybrid._block_heads(H, G, P * N * 4) == 16

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(states, x, Bs, Cs, dt, g, D, active):
        out = []
        for state in states:
            state, y = ssm_hybrid._ssm_step_pallas(state, x, Bs, Cs, dt, g,
                                                   D, active)
            out.append(state)
            x = x + y
        return out, x

    lowered = jax.jit(step, donate_argnums=(0,)).lower(
        [arg((1, B, H, P, N))] * L, arg((B, H, P)), arg((B, G, N)),
        arg((B, G, N)), arg((B, H)), arg((B, H)), arg((H,)),
        arg((B,), jnp.bool_))
    assert chip.compiled_by_mosaic(lowered.as_text())
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == L
    assert "ssm_state/pallas_call" in text
    layer_state = B * H * P * N * 4
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == L * layer_state
    assert memory.temp_size_in_bytes < layer_state // 16


# ----------------- the state-space expert decoder's step (ISSUE 55)
def test_the_sixth_blocks_step_chooses_by_shape_for_v5e(
        one_chip, compiled_mode):
    """``ssm_moe``'s whole decode step as a TPU process builds it, at
    ``nano``'s depth (``mamba mamba attention mamba``) around Granite's
    heads: 32 query heads over 8 KV heads of 128 (pages of 16 x 8 rows)
    and 8 state heads of ``[64, 128]`` float32 in ONE group. Both
    choices are IMPORTED and made by shape: ``kda_moe``'s attention
    kernel once in the ATTENTION layer under
    ``decode_step/smoe.attention``, the path the benchmark's reader
    looks for; the recurrence at ONE lane tile a row is the kernel of
    the ``N``-major entry (``ssm_hybrid.state_kernel``, ISSUE 56: the
    pool holds the state ``[H / 2, N, 2 P]``), once a MAMBA layer under
    ``decode_step/ssm.state``, every per-slot entry of the donated pool
    out in place; the expert layer's three scopes and the tied head's
    are there; and nothing raises."""
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu._private import chip
    from ray_tpu.models import ssm_hybrid, ssm_moe

    cfg = dataclasses.replace(
        ssm_moe.CONFIGS["nano"], d_model=256, n_head=32, n_kv_head=8,
        head_dim=128, attn_mult=1 / 128, ssm_heads=8, ssm_head_dim=64,
        ssm_state=128, d_expert=128, d_shared=256)
    B, ps, n_pages = 8, 16, 32

    def arg(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda k: ssm_moe.init_params(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: ssm_moe.init_paged_cache(cfg, B, n_pages, ps))
    args = (params, cache, jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((B, n_pages // B), jnp.int32))
    lowered = jax.jit(functools.partial(
        ssm_moe._slot_decode_step_paged, cfg=cfg, page_size=ps),
        donate_argnums=(1,)).lower(*jax.tree.map(arg, args))
    assert ssm_hybrid.lane_heads(cfg) == 2 and ssm_hybrid.state_kernel(cfg)
    assert cache["state0"].shape == (1, B, 4, 128, 128)
    assert ssm_hybrid.state_kernel(dataclasses.replace(cfg, ssm_state=256))
    assert not ssm_hybrid.state_kernel(
        dataclasses.replace(cfg, ssm_state=384))
    assert ssm_moe.decode_attention_fused(cfg, ps)
    assert chip.compiled_by_mosaic(lowered.as_text())
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "decode_step/smoe.attention/gqa_attention/pallas_call" in text
    assert "decode_step/ssm.state/ssm_state/pallas_call" in text
    assert text.count('custom_call_target="tpu_custom_call"') \
        == 1 + len(cfg.ssm_layers)
    for scope in ("ssm.state", "ssm.proj", "smoe.attention", "moe.route",
                  "moe.experts", "moe.shared", "lm.head"):
        assert f"decode_step/{scope}/" in text, scope
    layer_state = B * 8 * 64 * 128 * 4
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 3 * layer_state


@pytest.mark.parametrize("kernel,state_dtype", [
    ("ssm_step_pallas_nmajor", "float32"),
    ("ssm_step_pallas_nmajor", "bfloat16"), ("ssm_step_pallas", "float32")])
def test_ssm_state_kernel_compiles_at_the_sixth_blocks_shape_for_v5e(
        one_chip, compiled_mode, kernel, state_dtype):
    """The recurrence's kernels at granite-4.0-h-small's widths: 128
    heads of a ``[64, 128]`` float32 state in ONE group (a block lies
    inside the group and reads the one ``B`` and ``C``), nine layers of
    128 slots, one entry each: one ``tpu_custom_call`` a layer, every
    layer's whole entry its kernel's operand AND result. The step takes
    ``ssm_step_pallas_nmajor`` there, on the entry held ``[64, 128,
    128]`` (ISSUE 56: blocks of 32 rows of two heads, 2 MiB, in
    float32 as the cell states it, and of all 64 rows in bfloat16,
    whose rows of 128 are whole tiles too); the
    kernel of the ``[H, P, N]`` entry COMPILES at this shape too (blocks
    of 64 heads), though no step takes it below two lane tiles a row
    (``state_kernel``: bound by its own arithmetic there)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import chip
    from ray_tpu.models import ssm_hybrid

    B, L, H, G, P, N = 128, 9, 128, 1, 64, 128
    size = jnp.dtype(state_dtype).itemsize
    if kernel == "ssm_step_pallas_nmajor":
        entry = (1, B, H // 2, N, 2 * P)
        assert ssm_hybrid.block_heads(H // 2, G, N * 2 * P * size) \
            == 128 // size
    else:
        entry = (1, B, H, P, N)
        # 2 MiB a block: 64 of its heads, where Falcon-H1's are 16
        assert ssm_hybrid.block_heads(H, G, P * N * 4) == 64

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(states, x, Bs, Cs, dt, g, D, active):
        out = []
        for state in states:
            state, y = getattr(ssm_hybrid, kernel)(state, x, Bs, Cs, dt, g,
                                                   D, active)
            out.append(state)
            x = x + y
        return out, x

    lowered = jax.jit(step, donate_argnums=(0,)).lower(
        [arg(entry, state_dtype)] * L, arg((B, H, P)), arg((B, G, N)),
        arg((B, G, N)), arg((B, H)), arg((B, H)), arg((H,)),
        arg((B,), jnp.bool_))
    assert chip.compiled_by_mosaic(lowered.as_text())
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == L
    assert "ssm_state/pallas_call" in text
    layer_state = B * H * P * N * size
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == L * layer_state
    assert memory.temp_size_in_bytes < layer_state // 16


def test_train_step_gathers_a_layer_in_the_scan_on_v5e(topo, monkeypatch):
    """``cgpt1b3-train-fsdp4``'s step at its widths (24 layers, d 2048,
    f 8192, 16 sequences, flash attention, ``remat="dots"``,
    ``loss_chunk`` 256; 1,024 tokens a sequence so that no table is
    ``[d, d]``) as the v5e compiler plans it for the four chips: every
    weight all-gather is ONE layer's matrix in bfloat16 inside a scan's
    body, six a pass; the layer's gradient is summed over the chips in
    the backward body; no collective carries the layer dimension or a
    whole batch's activations through the blocks; and the program fits
    a chip. The parent's plan held 3.38 GB of collective results a
    layer in the loops (the whole stack gathered again in EVERY layer,
    all-to-alls of ``[B, S, d]``); this one 0.48 GB."""
    import dataclasses
    import math

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.ops import flash_attention
    from ray_tpu.parallel import compiled_collectives, create_mesh

    # the kernel as a TPU process builds it: flash_attention holds the
    # decision under its own name, bound when it was first imported
    monkeypatch.setattr(flash_attention, "pallas_interpret", lambda: False)
    L, B, S = 24, 16, 1024
    cfg = dataclasses.replace(gpt.CONFIGS["1b"], n_layer=L, max_seq=S,
                              remat="dots", loss_chunk=256)
    d, f = cfg.d_model, cfg.d_ff
    mesh = create_mesh({"fsdp": 4}, devices=list(topo.devices))
    init, step, state_sh, batch_sh = gpt.make_train_step(cfg, mesh)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, state_sh)
    tokens = jax.ShapeDtypeStruct((B, S + 1), jnp.int32, sharding=batch_sh)
    compiled = step.lower(state, {"tokens": tokens}).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 3     # flash, compiled
    plan = compiled_collectives(compiled)

    def dims(e):
        return [tuple(x for x in dd if x != 1) for _dt, dd in e["shapes"]]

    matrices = {(d, d), (d, f), (f, d)}
    assert not [e for e in plan for dd in dims(e) if L in dd]
    gathers = [e for e in plan if e["op"] == "all-gather"
               and set(dims(e)) & matrices]
    assert len(gathers) == 12, gathers
    assert all(e["in_loop"] for e in gathers), gathers
    assert {dt for e in gathers for dt, _ in e["shapes"]} == {"bf16"}
    assert sum(e["bytes"] for e in gathers) == 2 * 2 * (4 * d * d + 2 * d * f)
    sums = [e for e in plan if e["in_loop"] and e["bytes"] >= 4 * d * d
            and e["op"] in ("all-reduce", "reduce-scatter")]
    assert len(sums) >= 6, sums
    whole = [e for e in plan if e["in_loop"] for _dt, dd in e["shapes"]
             if math.prod(dd) in (B * S * d, B * S * f)]
    assert not whole, whole
    assert not [e for e in plan if e["in_loop"] and e["op"] == "all-to-all"]
    assert sum(e["bytes"] for e in plan if e["in_loop"]) < 0.6e9
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 0.9 * 16 * 2 ** 30
