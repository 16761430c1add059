"""Decode's latent attention as a Pallas kernel (ROADMAP S5e): the
kernel of ``ray_tpu/models/mla_moe.py`` against the XLA body it
replaces wherever Mosaic can address a page, which stays in the file
as the fallback and as this file's oracle.

The contract under test:

- the kernel (interpreted here: tier-1 exercises the REAL body) reads a
  lane's live tokens once, in blocks, in one softmax pass, and rounds
  its probabilities before the division by their sum where the XLA body
  rounds them after it: the two agree to a WRITTEN BOUND,
  ``mla_moe.ATTN_KERNEL_ULPS`` bf16 ulps of the largest output, across
  page sizes and lengths from nothing to several blocks, with the lanes'
  blocks fetched as one stream across lanes of every length;
- nothing outside a lane's live positions can move an output bit: not
  a stale latent in a page past the live length, not ``inf`` or ``NaN``
  there; a lane with nothing live (a row of sentinels, an inactive
  lane) fetches nothing and reads zeros;
- the step takes the kernel by what it can see (no knob), and in
  float32 its logits are the fallback's to 1e-4.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import mla_moe as mm, serving

CFG = mm.CONFIGS["nano"]
T = mm._ATTN_BLOCK_TOKENS
#: Positions a lane's table reaches: two whole blocks and half a third.
V = 2 * T + T // 2


def _ulps(out, ref):
    """|out - ref| in bf16 ulps of the largest reference output."""
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    return np.abs(out - ref).max() / (np.abs(ref).max() * 2.0 ** -8)


def _case(ps, seed=0):
    """Lanes of every kind, in an order that puts an empty lane first,
    between and last in the stream of blocks: ``(q, pool, pt, pos,
    active, live)`` with ``live`` the tokens each lane may read."""
    rng = np.random.default_rng(seed)
    max_pages = V // ps
    #     sentinels, 1, a page's edge, mid-page, inactive, a block's
    #     edge, one past it, full, mid-block, inactive
    pos = [3, 0, ps - 1, ps + ps // 2, 40, T - 1, T, V - 1, T + 77, 9]
    active = [True, True, True, True, False, True, True, True, True,
              False]
    B = len(pos)
    need = [0] + [p // ps + 1 for p in pos[1:]]
    n_pages = sum(need) + 7
    perm, off = rng.permutation(n_pages), 0
    pt = np.full((B, max_pages), mm.PT_SENTINEL, np.int32)
    for b, n in enumerate(need):
        pt[b, :n] = perm[off:off + n]
        off += n
    live = [p + 1 if a and n else 0
            for p, a, n in zip(pos, active, need)]
    pool = jnp.asarray(rng.standard_normal((n_pages, ps, CFG.latent_row)),
                       CFG.dtype)
    q = jnp.asarray(rng.standard_normal((B, CFG.n_head, CFG.latent_row)),
                    CFG.dtype)
    return q, pool, pt, np.asarray(pos, np.int32), \
        np.asarray(active), np.asarray(live)


def _both(q, pool, pt, pos, active, ps):
    """(kernel, XLA body) on the operands the step gives them."""
    n_pages = pool.shape[0]
    pages = jnp.clip(jnp.asarray(pt), 0, n_pages - 1)
    length = serving.live_length(jnp.asarray(pt), jnp.asarray(pos),
                             jnp.asarray(active), n_pages, ps)
    out = mm._latent_attention_pallas(q, pool, pages, length, CFG, ps)
    ref = mm._latent_attention_gather(q, pool, pages, jnp.asarray(pos),
                                      CFG, ps)
    return np.asarray(out, np.float32), np.asarray(ref, np.float32), \
        np.asarray(length)


@pytest.mark.parametrize("ps", [4, 16])
def test_kernel_agrees_with_the_xla_body_on_lanes_of_every_kind(ps):
    """Lengths 0 (a row of sentinels; an inactive lane beside active
    ones), 1, a page's edge, mid-page, a block's edge and one past it,
    ``max_pages`` full: the live lanes within the written bound of the
    XLA body, the others exactly zero."""
    q, pool, pt, pos, active, live = _case(ps)
    out, ref, length = _both(q, pool, pt, pos, active, ps)
    assert np.array_equal(length, live)
    assert np.isfinite(out).all()
    assert (out[live == 0] == 0).all()
    assert out.shape == (len(pos), CFG.n_head, CFG.kv_rank)
    assert _ulps(out[live > 0], ref[live > 0]) <= mm.ATTN_KERNEL_ULPS


@pytest.mark.parametrize("bad", [1e4, np.inf, np.nan], ids=str)
@pytest.mark.parametrize("ps", [4, 16])
def test_nothing_past_the_live_length_moves_an_output_bit(ps, bad):
    """A stale latent in a page past the live length, in a live page
    past ``pos``, or in a page no lane maps: the kernel never fetches
    the first and the last and masks the second (scores AND latents:
    0 * inf is NaN)."""
    q, pool, pt, pos, active, live = _case(ps, seed=1)
    out, _ref, _ = _both(q, pool, pt, pos, active, ps)
    stale = np.ones(pool.shape[:2], bool)
    for b, n in enumerate(live):
        for t in range(n):
            stale[pt[b, t // ps], t % ps] = False
    poisoned = jnp.where(jnp.asarray(stale)[..., None],
                         jnp.asarray(bad, pool.dtype), pool)
    out2, _ref, _ = _both(q, poisoned, pt, pos, active, ps)
    assert np.array_equal(out2, out)


def test_a_mapped_prefix_shorter_than_pos_cuts_the_length():
    """``pos + 1`` is cut to the mapped prefix of the lane's row, as
    the GPT kernel's length is: a hole ends what a lane reads."""
    ps = 4
    pt = np.full((2, 6), mm.PT_SENTINEL, np.int32)
    pt[0, :2] = [1, 0]
    pt[1, :1] = [2]
    pt[1, 2] = 3                     # behind a hole: never read
    length = serving.live_length(jnp.asarray(pt), jnp.asarray([20, 9]),
                             jnp.asarray([True, True]), 5, ps)
    assert list(np.asarray(length)) == [8, 4]


@pytest.mark.parametrize("ps", [4, 16])
def test_the_step_takes_the_kernel_and_stays_by_the_fallback(
        ps, monkeypatch):
    """``_slot_decode_step_paged`` with the kernel (what it takes here,
    interpreted) against the same step with the choice forced to the
    XLA body, in float32 so that no rounding of the probabilities
    stands between the two and a lane reading one token too few or
    another lane's page would show: the active lanes' logits within
    1e-4 of the largest."""
    cfg = dataclasses.replace(CFG, experts_held=8, dtype=jnp.float32,
                              param_dtype=jnp.float32)
    params = mm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    B, max_pages = 5, 48 // ps
    cache = mm.init_paged_cache(cfg, B, B * max_pages, ps)
    cache["latent"] = jnp.asarray(
        rng.standard_normal(cache["latent"].shape), cfg.dtype)
    cache["pos"] = jnp.asarray([0, ps - 1, 21, 47, 30], jnp.int32)
    pt = rng.permutation(B * max_pages).astype(np.int32).reshape(
        B, max_pages)
    token = jnp.asarray(rng.integers(0, cfg.vocab_size, (B,)), jnp.int32)
    active = jnp.asarray([True, True, True, True, False])

    def step():
        # a function of its own each time: a trace is cached by it
        return jax.jit(lambda: mm._slot_decode_step_paged(
            params, cache, token, active, jnp.asarray(pt), cfg, ps))

    assert mm.decode_attention_fused(cfg, ps)
    assert "pallas_call" in str(jax.make_jaxpr(step())())
    got, cache_k, counts_k = step()()
    monkeypatch.setattr(mm, "decode_attention_fused",
                        lambda *a, **k: False)
    assert "pallas_call" not in str(jax.make_jaxpr(step())())
    want, cache_x, counts_x = step()()
    got, want = np.asarray(got)[:4], np.asarray(want)[:4]
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    assert np.array_equal(np.asarray(cache_k["pos"]),
                          np.asarray(cache_x["pos"]))
    # every layer's row is the token's own latent, written before the
    # layer's attention: only layer 0's cannot depend on the path
    assert np.array_equal(
        np.asarray(cache_k["latent"][0], np.float32),
        np.asarray(cache_x["latent"][0], np.float32))
    assert counts_k[0] == counts_x[0]


def test_the_choice_is_made_from_what_the_program_can_see(monkeypatch):
    """Interpreted, any page is addressable; compiled for a TPU a page
    must be whole sublane tiles of the pool's dtype: 16 rows of
    bfloat16, 8 of float32. The knob has one value and no say."""
    from ray_tpu._private import chip

    f32 = dataclasses.replace(CFG, dtype=jnp.float32)
    assert mm.ATTN_KERNELS == ("gather",)
    assert mm.decode_attention_fused(CFG, 4, "gather")
    monkeypatch.setattr(chip, "pallas_interpret", lambda: False)
    assert [mm.decode_attention_fused(CFG, ps) for ps in (4, 8, 16, 32)] \
        == [False, False, True, True]
    assert [mm.decode_attention_fused(f32, ps) for ps in (4, 8, 16)] \
        == [False, True, True]
