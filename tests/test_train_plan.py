"""The plan THE COMPILER MADE of the fsdp training step, read from the
compiled program's text with ``parallel.sharding.compiled_collectives``
— not the rules, which only ask. A stacked block matrix is sharded on
its own dimensions, never on the scanned layer dimension, and each
layer's matrices are gathered by name where the layer runs
(``models/gpt.py _gather_layer``): so every weight all-gather is of ONE
layer's matrix and stands inside the scan's body, the gradient is
summed there too, the activations stay batch-sharded, and no gathered
copy outlives its layer.

The CPU's compiler is a stand-in: it hoists or sinks differently from
the TPU's (which SANK the old whole-stack gathers into the loop, 24
gathers of the whole stack a pass) and it widens bfloat16 collectives
to float32, so the dtype a gather moves is asserted on the lowered
text here and on the chip's own text in
``tests/test_gpt_decode_kernel_tpu.py``."""
import dataclasses
import math

import pytest

L, B, S, D, F, V = 8, 16, 64, 128, 512, 384


def _cfg(n_layer=L, **kw):
    from ray_tpu.models import gpt

    return dataclasses.replace(
        gpt.CONFIGS["nano"], n_layer=n_layer, d_model=D, d_ff=F, n_head=2,
        vocab_size=V, max_seq=S, remat="dots", loss_chunk=32, **kw)


def _lower(cfg, batch=B, seq=S):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.parallel import create_mesh

    mesh = create_mesh({"fsdp": 4}, devices=jax.devices()[:4])
    init, step, state_sh, batch_sh = gpt.make_train_step(cfg, mesh)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, state_sh)
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32,
                                  sharding=batch_sh)
    return step.lower(state, {"tokens": tokens})


@pytest.fixture(scope="module")
def lowered():
    return _lower(_cfg())


@pytest.fixture(scope="module")
def plan(lowered):
    from ray_tpu.parallel import compiled_collectives

    return compiled_collectives(lowered.compile())


def _dims(entry):
    return [dims for _dt, dims in entry["shapes"]]


MATRICES = {(D, D), (D, F), (F, D)}


def _matrix(dims):
    """A collective's result that is one layer's matrix (a leading 1 is
    the slice's own)."""
    return tuple(d for d in dims if d != 1) in MATRICES


def test_no_collective_carries_the_layer_dimension(plan):
    # L is no other size of this model, global or per device
    assert plan
    stacked = [e for e in plan for dims in _dims(e) if L in dims]
    assert not stacked, stacked


def test_weight_gathers_are_one_layer_inside_the_scan(plan):
    gathers = [e for e in plan if e["op"] == "all-gather"
               and any(_matrix(d) for d in _dims(e))]
    # six matrices a layer, in the forward and in the backward body
    assert len(gathers) == 12, gathers
    assert all(e["in_loop"] for e in gathers), gathers
    layer = 4 * D * D + 2 * D * F
    for e in gathers:
        assert math.prod(_dims(e)[0]) <= D * F
    assert sum(math.prod(_dims(e)[0]) for e in gathers) == 2 * layer
    # and their gradients are summed over the chips in the loop as well
    sums = [e for e in plan if e["op"] in ("all-reduce", "reduce-scatter")
            and e["in_loop"] and any(_matrix(d) for d in _dims(e))]
    assert sum(1 for e in sums for d in _dims(e) if _matrix(d)) == 6, sums


def test_gather_moves_the_compute_dtype(lowered):
    """The cast comes BEFORE the gather: the constraint the step asks
    for by name is on ``cfg.dtype`` values, six a block body."""
    import re

    text = lowered.as_text()
    asked = re.findall(
        r"sdy\.sharding_constraint[^\n]*tensor<(\d+)x(\d+)x(\w+)>", text)
    assert asked, "no sharding constraint in the lowered step"
    kernels = [(int(a), int(b), dt) for a, b, dt in asked
               if (int(a), int(b)) in MATRICES]
    assert kernels and {dt for _a, _b, dt in kernels} == {"bf16"}, asked


def test_activations_stay_batch_sharded(plan):
    """No collective's result is an activation of the whole batch:
    ``[B, S, d]`` / ``[B, S, f]`` (or a head-split view of them)."""
    whole = [e for e in plan for dims in _dims(e)
             if math.prod(dims) in (B * S * D, B * S * F)]
    assert not whole, whole
    in_blocks = [e for e in plan if e["in_loop"]
                 and e["op"] == "all-to-all"]
    assert not in_blocks, in_blocks   # xla attention here: none at all


def test_temporaries_do_not_keep_gathered_layers():
    """A gathered copy is no residual of the scan: four more layers
    cost their residuals and their sharded gradients, less than ONE
    layer's gathered bytes each (the old whole-stack plan: 2.3x; the
    gather outside ``jax.checkpoint``: 2.7x)."""
    d, f = 256, 1024

    def temp(n_layer):
        from ray_tpu.models import gpt

        cfg = dataclasses.replace(
            gpt.CONFIGS["nano"], n_layer=n_layer, d_model=d, d_ff=f,
            n_head=2, vocab_size=V, max_seq=S, remat="dots", loss_chunk=8)
        return _lower(cfg, batch=4, seq=8).compile() \
            .memory_analysis().temp_size_in_bytes

    gathered = (4 * d * d + 2 * d * f) * 2          # one layer, bfloat16
    assert (temp(8) - temp(4)) / 4 < gathered


def test_a_mesh_without_fsdp_asks_for_nothing():
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.parallel import create_mesh

    mesh = create_mesh({"dp": 8})
    p = {"wq": {"kernel": jax.numpy.ones((D, D))}}
    assert gpt._gather_layer(p, _cfg(), mesh) is p
    assert gpt._gather_layer(p, _cfg(), None) is p


def test_reader_on_hand_written_text():
    """``compiled_collectives`` on a text small enough to read: an
    asynchronous pair counts once with its RESULT's shape, a combined
    all-reduce keeps every array, a fusion called from a while body is
    inside the loop, and copies that share a channel count once."""
    from ray_tpu.parallel import compiled_collectives

    text = """
HloModule m

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%fused.1 (p: bf16[4,8]) -> bf16[16,8] {
  %p = bf16[4,8]{1,0} parameter(0)
  ROOT %ag.2 = bf16[16,8]{1,0} all-gather(%p), channel_id=3, dimensions={0}
}

%body (t: (s32[], bf16[4,8])) -> (s32[], bf16[4,8]) {
  %t = (s32[], bf16[4,8]{1,0}) parameter(0)
  %w = bf16[4,8]{1,0} get-tuple-element(%t), index=1
  %f = bf16[16,8]{1,0} fusion(%w), kind=kCustom, calls=%fused.1
  %ag.3 = bf16[16,8]{1,0} all-gather(%w), channel_id=3, dimensions={0}
  %st = (bf16[4,8]{1,0}, bf16[16,8]{1,0}) all-gather-start(%w), channel_id=4, dimensions={0}
  %dn = bf16[16,8]{1,0} all-gather-done(%st)
  %ar = (f32[8]{0}, /*index=1*/f32[2,8]{1,0}) all-reduce(%x, %y), channel_id=5, to_apply=%add
  %as = (f32[8]{0}, f32[4]{0}) all-reduce-start(%x, %z), channel_id=6, to_apply=%add
  %ad = (f32[8]{0}, f32[4]{0}) all-reduce-done(%as)
  ROOT %o = (s32[], bf16[4,8]{1,0}) tuple(%i, %w)
}

%cond (t: (s32[], bf16[4,8])) -> pred[] {
  %t = (s32[], bf16[4,8]{1,0}) parameter(0)
  ROOT %c = pred[] constant(true)
}

ENTRY %main (x: bf16[4,8]) -> bf16[4,8] {
  %x = bf16[4,8]{1,0} parameter(0)
  %rs = f32[2,8]{1,0:T(8,128)} reduce-scatter(%g), channel_id=9, dimensions={0}, to_apply=%add
  %w0 = (s32[], bf16[4,8]{1,0}) while(%init), condition=%cond, body=%body
  ROOT %r = bf16[4,8]{1,0} get-tuple-element(%w0), index=1
}
"""
    got = compiled_collectives(text)
    assert [(e["op"], e["computation"], e["in_loop"], e["shapes"],
             e["bytes"]) for e in got] == [
        ("all-gather", "fused.1", True, [("bf16", (16, 8))], 256),
        ("all-gather", "body", True, [("bf16", (16, 8))], 256),
        ("all-reduce", "body", True, [("f32", (8,)), ("f32", (2, 8))], 96),
        ("all-reduce", "body", True, [("f32", (8,)), ("f32", (4,))], 48),
        ("reduce-scatter", "main", False, [("f32", (2, 8))], 64),
    ]
    assert got[1]["name"] == "st"
