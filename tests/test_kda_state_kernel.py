"""The gated delta-rule recurrence as a Pallas kernel (ROADMAP S5f): the
kernel of ``ray_tpu/models/kda_moe.py`` against ``_kda_step``, the XLA
body it replaces wherever Mosaic can address a head's state, which
stays in the file as the fallback and as this file's oracle.

The contract under test:

- the kernel (interpreted here: tier-1 exercises the REAL body) takes
  both sums, the decay, the rank-one update and ``o`` from ONE copy of
  a block of a live lane's heads, in float32. It sums over ``dk`` in
  another order than XLA's reduction does, so the two agree to a
  WRITTEN BOUND, :data:`REL` of the largest value, not bit for bit
  (and tokens downstream are no measure of it: ROADMAP D10);
- an inactive lane's state comes out BIT FOR BIT as it went in, as do
  the layers of the ``[n_kda, slots, ...]`` entry the call does not
  name; an inactive lane reads ``o`` = 0;
- the step takes the kernel by what it can see (no knob, no new
  ``attn_kernel`` name) and says which through the description's
  ``decode_attention_fused``; the engine reports and counts it.
"""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import kda_moe as km
from ray_tpu.models import serving
from ray_tpu.serve.engine import DecodeEngine

#: Kernel and oracle hold the same float32 products and add them in
#: another order: ``dk`` <= 16 addends of one sign-mixed sum, each
#: rounded to 2^-24 of the partial sum, far under 1e-5 of the largest
#: value; a bfloat16 product anywhere over ``S`` would read 4e-3.
REL = 1e-5
L, H, D = 3, 6, 16


def _inputs(B, seed):
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(B, H, D))) * D ** -0.5
    k = unit(rng.normal(size=(B, H, D)))
    v = rng.normal(size=(B, H, D))
    g = -rng.uniform(0.001, 3.0, size=(B, H, D))
    beta = rng.uniform(0.0, 2.0, size=(B, H))
    state = rng.normal(size=(L, B, H, D, D))
    return tuple(jnp.asarray(a, jnp.float32)
                 for a in (state, q, k, v, g, beta))


def _rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() / np.abs(want).max()


MASKS = {
    "all-live": lambda B: np.ones((B,), bool),
    # the first lane and, where there is one, a lane in the middle
    "some-parked": lambda B: np.arange(B) % 3 != 0 if B > 1
    else np.zeros((B,), bool),
    "last-live": lambda B: np.arange(B) == B - 1,
    "all-idle": lambda B: np.zeros((B,), bool),
}


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("heads_a_block", [1, 2, 6])
@pytest.mark.parametrize("B", [1, 3, 8])
def test_the_kernel_is_the_recurrence_on_the_live_lanes_of_one_layer(
        monkeypatch, B, heads_a_block, mask):
    monkeypatch.setattr(km, "_KDA_BLOCK_HEADS", heads_a_block)
    state, q, k, v, g, beta = _inputs(B, seed=B)
    active = MASKS[mask](B)
    layer = 1
    got_state, got_o = jax.jit(
        lambda s, a: km._kda_step_pallas(s, layer, q, k, v, g, beta, a))(
        state, jnp.asarray(active))
    want_S, want_o = km._kda_step(state[layer], q, k, v, g, beta)
    got_state, state = np.asarray(got_state), np.asarray(state)
    # the right layer of the entry, and no other
    assert np.array_equal(got_state[[0, 2]], state[[0, 2]])
    # an inactive lane: not a bit of its state, and o = 0
    assert np.array_equal(got_state[layer][~active], state[layer][~active])
    assert not np.asarray(got_o)[~active].any()
    if active.any():
        assert _rel(got_state[layer][active],
                    np.asarray(want_S)[active]) < REL
        assert _rel(np.asarray(got_o)[active],
                    np.asarray(want_o)[active]) < REL
        assert not np.array_equal(got_state[layer][active],
                                  state[layer][active])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_state_keeps_its_dtype_and_the_arithmetic_is_float32(dtype):
    """A state held in bfloat16 (``state_dtype``) is widened in the
    kernel, as the XLA path widens it, and rounded once on the way
    back."""
    state, q, k, v, g, beta = _inputs(3, seed=11)
    state = state.astype(dtype)
    active = jnp.asarray([True, False, True])
    got_state, got_o = km._kda_step_pallas(
        state, 0, q, k, v, g, beta, active)
    want_S, want_o = km._kda_step(state[0].astype(jnp.float32), q, k, v,
                                  g, beta)
    assert got_state.dtype == dtype
    live = np.asarray(active)
    assert _rel(np.asarray(got_o)[live], np.asarray(want_o)[live]) < REL
    ulp = 2.0 ** -8 if dtype == jnp.bfloat16 else REL
    assert _rel(got_state[0].astype(jnp.float32)[live],
                np.asarray(want_S)[live]) <= ulp


def test_the_live_lanes_ride_in_lane_order_then_the_last_again():
    lanes, n = serving.live_lanes(jnp.asarray([False, True, False, True,
                                           True, False]))
    assert list(np.asarray(lanes)) == [1, 3, 4, 4, 4, 4]
    assert list(np.asarray(n)) == [3]
    lanes, n = serving.live_lanes(jnp.zeros((4,), bool))
    assert list(np.asarray(lanes)) == [0, 0, 0, 0] and int(n[0]) == 0


def _step_pair(cfg, monkeypatch):
    """One decode step over three prefilled lanes with the kernel and
    with the fallback: ``((logits, cache), (logits, cache), held)``."""
    params = km.init_params(jax.random.PRNGKey(0), cfg)
    slots, ps, max_pages = 3, 4, 8
    cache = km.init_paged_cache(cfg, slots, slots * max_pages, ps)
    pt = np.arange(slots * max_pages, dtype=np.int32).reshape(slots, -1)
    rng = np.random.default_rng(5)
    prefill = km.jit_prefill_into_slot_paged(cfg, ps)
    for slot in range(slots):
        padded = np.zeros((1, 16), np.int32)
        padded[0, :9 + slot] = rng.integers(0, cfg.vocab_size, 9 + slot)
        _, cache, _ = prefill(params, cache, padded, np.int32(9 + slot),
                              np.int32(0), pt[slot],
                              np.int32(km.PT_SENTINEL), np.int32(slot),
                              jax.random.PRNGKey(0))
    held = jax.tree_util.tree_map(np.asarray, cache)
    active = np.array([True, False, True])
    out = []
    for fused in (True, False):
        monkeypatch.setattr(km, "_state_kernel",
                            lambda cfg, fused=fused: fused)
        step = jax.jit(functools.partial(km._slot_decode_step_paged,
                                         cfg=cfg, page_size=ps))
        logits, after, counts = step(params, dict(cache),
                                     jnp.asarray([5, 7, 9]), active,
                                     jnp.asarray(pt))
        assert int(counts[4]) == 2
        out.append((np.asarray(logits),
                    jax.tree_util.tree_map(np.asarray, after)))
    return out[0], out[1], held


def test_the_step_with_the_kernel_stays_by_the_step_with_the_fallback(
        monkeypatch):
    """In float32 (nothing rounds what the two sum differently) the
    live lanes' logits and the whole cache agree to :data:`REL`-sized
    bounds, and the parked lane's state and tail are the bits that
    went in, on both paths."""
    cfg = dataclasses.replace(km.CONFIGS["nano"], experts_held=8,
                              dtype=jnp.float32, param_dtype=jnp.float32)
    (lg_k, c_k), (lg_x, c_x), held = _step_pair(cfg, monkeypatch)
    assert _rel(lg_k[[0, 2]], lg_x[[0, 2]]) < 1e-4
    for name in ("state", "conv", "k", "v"):
        assert _rel(c_k[name], c_x[name]) < 1e-5
    for c in (c_k, c_x):
        for name in ("state", "conv"):
            assert np.array_equal(c[name][:, 1], held[name][:, 1])
            assert not np.array_equal(c[name][:, 0], held[name][:, 0])
    assert list(c_k["pos"]) == list(c_x["pos"])


def test_the_choice_is_made_from_what_the_program_can_see(monkeypatch):
    """Interpreted (here) any width is addressable; compiled for a TPU
    a head's state must be whole (8, 128) tiles, and a shape off the
    tile takes ``_kda_step``: the description says which, the program
    holds a ``pallas_call`` or none, and no knob has a say."""
    from ray_tpu._private import chip

    nano = km.CONFIGS["nano"]
    wide = dataclasses.replace(nano, kda_head_dim=128)
    assert km.decode_attention_fused(nano, 4)
    monkeypatch.setattr(chip, "pallas_interpret", lambda: False)
    assert not km.decode_attention_fused(nano, 4)
    assert not km.decode_attention_fused(
        dataclasses.replace(nano, kda_head_dim=192), 16)
    assert km.decode_attention_fused(wide, 16)
    assert km.decode_attention_fused(wide, 16, "gather")
    assert km.ATTN_KERNELS == ("gather",)
    with pytest.raises(ValueError, match="attn_kernel must be one of"):
        km.jit_decode_chunk_slots_paged(nano, 4, 4, attn_kernel="pallas")

    # what the description says is what the traced program holds
    def held(cfg):
        params = jax.eval_shape(
            lambda: km.init_params(jax.random.PRNGKey(0), cfg))
        cache = jax.eval_shape(lambda: km.init_paged_cache(cfg, 2, 8, 4))
        S = jax.ShapeDtypeStruct
        return "pallas_call" in str(jax.make_jaxpr(functools.partial(
            km._slot_decode_step_paged, cfg=cfg, page_size=4))(
            params, cache, S((2,), jnp.int32), S((2,), jnp.bool_),
            S((2, 4), jnp.int32)))

    assert not held(nano)                    # off the tile: _kda_step
    monkeypatch.undo()
    assert held(nano)                        # interpreted: the kernel


def test_the_engine_reports_the_kernel_and_counts_its_dispatches():
    cfg = dataclasses.replace(km.CONFIGS["nano"], experts_held=8)
    eng = DecodeEngine(km.init_params(jax.random.PRNGKey(0), cfg), cfg,
                       slots=2, chunk=4, max_len=96,
                       prompt_buckets=(16, 32), page_size=4, n_pages=48)
    try:
        assert eng.warm_up()["attn_kernel_mode"] == "interpret"
        prompt = np.arange(11, dtype=np.int32)
        got = np.concatenate(list(eng.stream(prompt, 9)))
        assert got.shape == (9,)
        st = eng.stats()
        assert st["attn_kernel_dispatches"] >= 2     # 9 tokens, chunk 4
        assert st["attn_kernel_dispatches"] == st["dispatches"]
        assert st["state_lanes_sum"] >= 8
    finally:
        eng.shutdown()
