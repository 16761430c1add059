"""Decode's grouped-query attention as a Pallas kernel (ROADMAP S5g):
the kernel of ``ray_tpu/models/kda_moe.py`` against
``_gqa_attention_gather``, the XLA body it replaces wherever Mosaic can
address a page and its heads, which stays in the file as the fallback
and as this file's oracle.

The contract under test:

- the kernel (interpreted here: tier-1 exercises the REAL body) reads a
  lane's live tokens once, in blocks whose rows are ``(token, KV
  head)`` as a page holds them, all query heads against all rows with
  the foreign heads' columns masked, in one softmax pass; it rounds its
  probabilities before the division by their sum where the XLA body
  rounds them after it: the two agree to a WRITTEN BOUND,
  ``kda_moe.ATTN_KERNEL_ULPS`` bf16 ulps of the largest output, across
  page sizes, group sizes and lengths from nothing to the full table,
  with the lanes' blocks fetched as one stream;
- nothing outside a lane's live positions can move an output bit: not
  a stale key or value in a page past the live length, not ``inf`` or
  ``NaN`` there; a lane with nothing live (a row of sentinels, an
  inactive lane) fetches nothing and reads zeros;
- the step takes the kernel by what it can see (no knob), in float32
  its logits are the fallback's to 1e-4, and it says what it fetched:
  ``gqa_tokens_read_sum``, the live tokens in whole pages with the
  kernel and every lane's whole table row without.
"""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import kda_moe as km, serving

#: 8 query heads over 2 KV heads (groups of 4), then the cell's group
#: of 8 over one head more than a power of two, then the parallel
#: hybrid's heads (``models/ssm_hybrid.py`` through the public entry):
#: 20 queries, no whole sublane tile, in groups of 5.
CFGS = {
    "g4": dataclasses.replace(km.CONFIGS["nano"], n_head=8, n_kv_head=2),
    "g8x3": dataclasses.replace(km.CONFIGS["nano"], n_head=24,
                                n_kv_head=3),
    "g5x4": dataclasses.replace(km.CONFIGS["nano"], n_head=20,
                                n_kv_head=4),
    # the state-space expert decoder's (``models/ssm_moe.py``): 32
    # queries over 8 KV heads, its own score scale folded into ``q``
    "g4x8": dataclasses.replace(km.CONFIGS["nano"], n_head=32,
                                n_kv_head=8),
}
T = 32                        # tokens a block in this file
#: Positions a lane's table reaches: two whole blocks and half a third.
V = 2 * T + T // 2


def _lanes(ps):
    """Lanes of every kind, by name: ``(pos, active, mapped)``; in this
    order an empty lane stands first, between and last in the stream."""
    return {
        "sentinels": (3, True, False),
        "one-token": (0, True, True),
        "page-less-one": (ps - 2, True, True),
        "a-page": (ps - 1, True, True),
        "inactive-mid": (40, False, True),
        "block-edge": (T - 1, True, True),
        "block-edge-plus-one": (T, True, True),
        "several-blocks": (T + T // 2 + 5, True, True),
        "full-table": (V - 1, True, True),
        "inactive-last": (9, False, True),
    }


LANES = list(_lanes(4))


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 32 tokens: a table of 80 positions is several."""
    monkeypatch.setattr(km, "_GQA_BLOCK_TOKENS", T)


def _ulps(out, ref):
    """|out - ref| in bf16 ulps of the largest reference output."""
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    return np.abs(out - ref).max() / (np.abs(ref).max() * 2.0 ** -8)


def _case(cfg, ps, seed=0):
    """``(q, kpool, vpool, pt, pos, active, live)`` over :func:`_lanes`,
    each lane's pages a draw from the pool, sentinels behind them;
    ``live`` is the tokens a lane may read."""
    rng = np.random.default_rng(seed)
    max_pages = V // ps
    pos, active, mapped = zip(*_lanes(ps).values())
    need = [p // ps + 1 if m else 0 for p, m in zip(pos, mapped)]
    B = len(pos)
    n_pages = sum(need) + 7
    perm, off = rng.permutation(n_pages), 0
    pt = np.full((B, max_pages), km.PT_SENTINEL, np.int32)
    for b, n in enumerate(need):
        pt[b, :n] = perm[off:off + n]
        off += n
    live = [p + 1 if a and n else 0 for p, a, n in zip(pos, active, need)]
    shape = (n_pages, ps, cfg.n_kv_head, cfg.head_dim)
    kpool = jnp.asarray(rng.standard_normal(shape), cfg.dtype)
    vpool = jnp.asarray(rng.standard_normal(shape), cfg.dtype)
    q = jnp.asarray(rng.standard_normal((B, cfg.n_head, cfg.head_dim)),
                    cfg.dtype)
    return q, kpool, vpool, pt, np.asarray(pos, np.int32), \
        np.asarray(active), np.asarray(live)


def _both(cfg, q, kpool, vpool, pt, pos, active, ps):
    """(kernel, XLA body, length) on the operands the step gives."""
    n_pages = kpool.shape[0]
    pages = jnp.clip(jnp.asarray(pt), 0, n_pages - 1)
    length = serving.live_length(jnp.asarray(pt), jnp.asarray(pos),
                             jnp.asarray(active), n_pages, ps)
    out = km._gqa_attention_pallas(q, kpool, vpool, pages, length,
                                   cfg.n_kv_head, ps)
    ref = km._gqa_attention_gather(q, kpool, vpool, pages, jnp.asarray(pos),
                                   cfg.n_kv_head, cfg.dtype, ps)
    return np.asarray(out), np.asarray(ref), np.asarray(length)


@functools.lru_cache(maxsize=None)
def _ran(name, ps):
    """One call of each body over every lane of :func:`_lanes`."""
    cfg = CFGS[name]
    *operands, live = _case(cfg, ps)
    out, ref, length = _both(cfg, *operands, ps)
    assert np.array_equal(length, live)
    assert out.shape == ref.shape == (len(LANES), cfg.n_head, cfg.head_dim)
    assert out.dtype == ref.dtype == np.float32
    return out, ref, live


@pytest.mark.parametrize("lane", sorted(LANES))
@pytest.mark.parametrize("ps", [4, 16])
@pytest.mark.parametrize("name", sorted(CFGS))
def test_kernel_agrees_with_the_xla_body_on_a_lane_of_this_kind(name, ps,
                                                               lane):
    """Lengths 0 (a row of sentinels; an inactive lane beside active
    ones), 1, a page less one, a page, a block's edge and one past it,
    several blocks, the full table: a live lane within the written
    bound of the XLA body (in ulps of the CALL's largest output),
    another exactly zero."""
    out, ref, live = _ran(name, ps)
    b = LANES.index(lane)
    assert np.isfinite(out[b]).all()
    if live[b] == 0:
        assert (out[b] == 0).all()
    else:
        assert np.abs(out[b] - ref[b]).max() <= km.ATTN_KERNEL_ULPS \
            * 2.0 ** -8 * np.abs(ref[live > 0]).max()


@pytest.mark.parametrize("bad", [1e4, np.inf, np.nan], ids=str)
@pytest.mark.parametrize("ps", [4, 16])
def test_nothing_past_the_live_length_moves_an_output_bit(ps, bad):
    """A stale key or value in a page past the live length, in a live
    page past ``pos``, or in a page no lane maps: the kernel never
    fetches the first and the last and masks the second (scores AND
    values: 0 * inf is NaN)."""
    cfg = CFGS["g4"]
    q, kpool, vpool, pt, pos, active, live = _case(cfg, ps, seed=1)
    out, _ref, _ = _both(cfg, q, kpool, vpool, pt, pos, active, ps)
    stale = np.ones(kpool.shape[:2], bool)
    for b, n in enumerate(live):
        for t in range(n):
            stale[pt[b, t // ps], t % ps] = False
    stale = jnp.asarray(stale)[..., None, None]
    out2, _ref, _ = _both(
        cfg, q, jnp.where(stale, jnp.asarray(bad, kpool.dtype), kpool),
        jnp.where(stale, jnp.asarray(bad, vpool.dtype), vpool), pt, pos,
        active, ps)
    assert np.array_equal(out2, out)


def test_a_head_attends_its_own_keys_and_no_other_heads():
    """The block's rows hold every KV head's keys: moving another
    head's keys and values moves no bit of a query head's output, and
    moving its own does."""
    cfg, ps = CFGS["g4"], 4
    q, kpool, vpool, pt, pos, active, live = _case(cfg, ps, seed=3)
    out, ref, _ = _both(cfg, q, kpool, vpool, pt, pos, active, ps)
    G = cfg.n_head // cfg.n_kv_head
    out2, _, _ = _both(cfg, q, kpool.at[:, :, 1].multiply(-2.0),
                       vpool.at[:, :, 1].add(1.0), pt, pos, active, ps)
    assert np.array_equal(out2[:, :G], out[:, :G])
    assert not np.array_equal(out2[live > 0, G:], out[live > 0, G:])
    assert _ulps(out[live > 0], ref[live > 0]) <= km.ATTN_KERNEL_ULPS


def _step(cfg, ps, kernel, monkeypatch):
    """One decode step over five lanes of a random cache with the
    choice of the GQA body forced: ``(logits, cache', counts, jaxpr)``."""
    params = km.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    B, max_pages = 5, 48 // ps
    cache = km.init_paged_cache(cfg, B, B * max_pages, ps)
    for name in ("k", "v", "state", "conv"):
        cache[name] = jnp.asarray(
            rng.standard_normal(cache[name].shape) * 0.3, cache[name].dtype)
    cache["pos"] = jnp.asarray([0, ps - 1, 21, 47, 30], jnp.int32)
    pt = rng.permutation(B * max_pages).astype(np.int32).reshape(
        B, max_pages)
    pt[2, 6:] = km.PT_SENTINEL            # pos 21: pages 0..5 of ps 4
    token = jnp.asarray(rng.integers(0, cfg.vocab_size, (B,)), jnp.int32)
    active = jnp.asarray([True, True, True, True, False])
    monkeypatch.setattr(km, "_gqa_kernel", lambda cfg, ps: kernel)
    # a function of its own each time: a trace is cached by it
    step = jax.jit(lambda: km._slot_decode_step_paged(
        params, cache, token, active, jnp.asarray(pt), cfg, ps))
    return step() + (str(jax.make_jaxpr(step)()),)


@pytest.mark.parametrize("ps", [4, 16])
def test_the_step_with_the_kernel_stays_by_the_step_with_the_fallback(
        ps, monkeypatch):
    """``_slot_decode_step_paged`` with the kernel against the same
    step with the choice forced to the XLA body, in float32 so that no
    rounding of the probabilities stands between the two and a lane
    reading one token too few or another lane's page would show: the
    active lanes' logits within 1e-4 of the largest, the cache the
    same."""
    cfg = dataclasses.replace(km.CONFIGS["nano"], experts_held=8,
                              n_head=8, dtype=jnp.float32,
                              param_dtype=jnp.float32)
    got, cache_k, _, jaxpr_k = _step(cfg, ps, True, monkeypatch)
    want, cache_x, _, jaxpr_x = _step(cfg, ps, False, monkeypatch)
    assert "gqa_attention" in jaxpr_k and "gqa_attention" not in jaxpr_x
    got, want = np.asarray(got)[:4], np.asarray(want)[:4]
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    # the one GQA layer is the first: what the step wrote into the
    # pages cannot depend on the path; the state follows the logits
    for name in ("k", "v", "pos"):
        assert np.array_equal(np.asarray(cache_k[name]),
                              np.asarray(cache_x[name]))
    assert np.abs(np.asarray(cache_k["state"] - cache_x["state"])[:, :4]
                  ).max() <= 1e-4 * np.abs(np.asarray(cache_x["state"])
                                           ).max()


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernel", "gather"])
@pytest.mark.parametrize("ps", [4, 16])
def test_the_step_counts_the_positions_its_attention_fetched(
        ps, kernel, monkeypatch):
    """``gqa_tokens_read_sum``, the sixth counter: with the kernel each
    active lane's ``pos + 1`` tokens rounded up to whole pages (the
    inactive lane nothing), every GQA layer; with the gather ``lanes x
    max_pages x page_size`` whatever is live. The five before it stand
    where they stood."""
    cfg = dataclasses.replace(km.CONFIGS["nano"], experts_held=8,
                              n_layer=6, gqa_layers=(0, 3))
    _, _, counts, _ = _step(cfg, ps, kernel, monkeypatch)
    assert km.STEP_COUNTERS[4:] == ("state_lanes_sum",
                                    "gqa_tokens_read_sum")
    assert counts.shape == (len(km.STEP_COUNTERS),)
    assert int(counts[0]) == cfg.n_layer and int(counts[4]) == 4
    pages = [p // ps + 1 for p in (0, ps - 1, 21, 47)]
    assert int(counts[5]) == 2 * (ps * sum(pages) if kernel else 5 * 48)


def test_the_choice_is_made_from_shapes_alone(monkeypatch):
    """Interpreted, any page is addressable; compiled for a TPU a head
    must be whole 128-lane tiles and a page's rows (``page_size x
    n_kv_head``) whole sublane tiles of the pool's dtype: 16 of
    bfloat16, 8 of float32. The knob has one value and no say, and
    ``decode_attention_fused`` answers for the program: either kernel
    makes it true."""
    from ray_tpu._private import chip

    nano = km.CONFIGS["nano"]
    wide = dataclasses.replace(nano, head_dim=128)          # 2 KV heads
    assert km.ATTN_KERNELS == ("gather",)
    assert km._gqa_kernel(nano, 4) and km._state_kernel(nano)
    monkeypatch.setattr(chip, "pallas_interpret", lambda: False)
    assert not km._gqa_kernel(nano, 16)                # heads of 16
    assert [km._gqa_kernel(wide, ps) for ps in (2, 4, 8, 16)] \
        == [False, False, True, True]
    f32 = dataclasses.replace(wide, dtype=jnp.float32)
    assert [km._gqa_kernel(f32, ps) for ps in (2, 4, 8)] \
        == [False, True, True]
    assert not km._gqa_kernel(dataclasses.replace(wide, head_dim=192), 16)
    # the program's answer: the recurrence's kernel OR the attention's
    assert not km._state_kernel(wide)
    assert km.decode_attention_fused(wide, 16, "gather")
    assert not km.decode_attention_fused(wide, 4)
    assert km.decode_attention_fused(
        dataclasses.replace(nano, kda_head_dim=128), 4)
    assert not km.decode_attention_fused(
        dataclasses.replace(wide, gqa_layers=()), 16)
