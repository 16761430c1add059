"""The Mamba-2 recurrence of a decode step as a Pallas kernel (ISSUE
53): the kernel of ``ray_tpu/models/ssm_hybrid.py`` against
``_ssm_step``, the XLA body it replaces wherever Mosaic can address a
head's state, which stays in the file as the fallback and as this
file's oracle.

The contract under test:

- the kernel (interpreted here: tier-1 exercises the REAL body) takes
  the sum ``S C``, the decay, the rank-one term and ``y`` from ONE copy
  of a block of a live lane's heads, in float32, ``y`` from the state
  BEFORE the step. It sums over ``N`` in another order than XLA's
  reduction does, so the two agree to a WRITTEN BOUND, :data:`REL` of
  the largest value, not bit for bit;
- an inactive lane's state comes out BIT FOR BIT as it went in and it
  reads ``y`` = 0; the entry keeps its dtype;
- a group's ``B`` and ``C`` reach that group's heads and no other,
  whether a block holds part of a group, one, or several;
- where a row of state is ONE lane tile (``ssm_state`` 128) the entry
  lies ``N``-major with the heads side by side on lanes
  (``lane_heads``, ``state_entry`` / ``state_heads``) and the kernel is
  ``ssm_step_pallas_nmajor`` (ISSUE 56), held to the same oracle and
  the same contract: one layout and one kernel a shape;
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

from ray_tpu.models import kda_moe, serving
from ray_tpu.models import ssm_hybrid as sh
from ray_tpu.serve.engine import DecodeEngine

#: Kernel and oracle hold the same float32 products and add them in
#: another order: ``N`` = 32 addends of one sign-mixed sum, each rounded
#: to 2^-24 of the partial sum, far under 1e-5 of the largest value; a
#: bfloat16 product anywhere over ``S`` would read 4e-3.
REL = 1e-5
H, G, P, N = 8, 2, 16, 32
#: bytes of one head's float32 state here: the kernel's block is capped
#: in BYTES (``_SSM_BLOCK_BYTES``), so a test that wants ``n`` heads a
#: block sets the cap to ``n`` heads' worth
HEAD = P * N * 4


def _inputs(B, seed, groups=G):
    rng = np.random.default_rng(seed)
    state = rng.normal(size=(1, B, H, P, N))
    x = rng.normal(size=(B, H, P))
    Bs = rng.normal(size=(B, groups, N))
    Cs = rng.normal(size=(B, groups, N))
    dt = rng.uniform(0.001, 0.2, size=(B, H))
    g = -rng.uniform(0.001, 3.0, size=(B, H))
    D = rng.normal(size=(H,)) + 1.0
    return tuple(jnp.asarray(a, jnp.float32)
                 for a in (state, x, Bs, Cs, dt, g, D))


def _oracle(state, x, Bs, Cs, dt, g, D):
    """``_ssm_step`` on the entry's one layer, ``B`` and ``C`` a head."""
    per = x.shape[1] // Bs.shape[1]
    return sh._ssm_step(state[0].astype(jnp.float32), x,
                        jnp.repeat(Bs, per, axis=1),
                        jnp.repeat(Cs, per, axis=1), dt, g, D)


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
@pytest.mark.parametrize("heads_a_block", [1, 2, 4, 8])
@pytest.mark.parametrize("B", [1, 3, 8])
def test_the_kernel_is_the_recurrence_on_the_live_lanes(
        monkeypatch, B, heads_a_block, mask):
    """Blocks of 1 and 2 heads lie inside a group of 4, one of 4 is a
    group, one of 8 both groups."""
    monkeypatch.setattr(sh, "_SSM_BLOCK_BYTES", heads_a_block * HEAD)
    assert sh._block_heads(H, G, HEAD) == heads_a_block
    state, *ops = _inputs(B, seed=B)
    active = MASKS[mask](B)
    got_state, got_y = jax.jit(
        lambda s, a: sh._ssm_step_pallas(s, *ops, a))(
        state, jnp.asarray(active))
    want_S, want_y = _oracle(state, *ops)
    got_state, state = np.asarray(got_state), np.asarray(state)
    assert got_state.shape == state.shape
    # an inactive lane: not a bit of its state, and y = 0
    assert np.array_equal(got_state[0][~active], state[0][~active])
    assert not np.asarray(got_y)[~active].any()
    if active.any():
        assert _rel(got_state[0][active], np.asarray(want_S)[active]) < REL
        assert _rel(np.asarray(got_y)[active],
                    np.asarray(want_y)[active]) < REL
        assert not np.array_equal(got_state[0][active], state[0][active])


@pytest.mark.parametrize("heads_a_block", [8, 16, 32])
def test_heads_of_64_by_128_in_one_group_as_the_sixth_block_holds_them(
        monkeypatch, heads_a_block):
    """The kernel of the ``[H, P, N]`` entry at the state-space expert
    decoder's heads (no step takes it there since ISSUE 56: that
    model's entry lies ``N``-major, the tests below; the body is right
    at any shape all the same): heads of ``[64, 128]``, ALL in one
    group, so that every block lies inside the group and reads the one
    ``B`` and ``C``; 32 heads here (128 there) in blocks of 8, 16 and
    all 32, a parked lane between two live ones."""
    heads, P_, N_, B = 32, 64, 128, 3
    monkeypatch.setattr(sh, "_SSM_BLOCK_BYTES",
                        heads_a_block * P_ * N_ * 4)
    assert sh.block_heads(heads, 1, P_ * N_ * 4) == heads_a_block
    rng = np.random.default_rng(heads_a_block)
    state, x, Bs, Cs = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                        for shape in ((1, B, heads, P_, N_),
                                      (B, heads, P_), (B, 1, N_),
                                      (B, 1, N_)))
    dt = jnp.asarray(rng.uniform(0.001, 0.2, (B, heads)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.001, 3.0, (B, heads)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(heads,)) + 1.0, jnp.float32)
    active = np.array([True, False, True])
    got_state, got_y = jax.jit(sh.ssm_step_pallas)(
        state, x, Bs, Cs, dt, g, D, jnp.asarray(active))
    want_S, want_y = sh.ssm_step(
        state[0], x, jnp.repeat(Bs, heads, axis=1),
        jnp.repeat(Cs, heads, axis=1), dt, g, D)
    got_state = np.asarray(got_state)
    assert np.array_equal(got_state[0, 1], np.asarray(state)[0, 1])
    assert not np.asarray(got_y)[1].any()
    assert _rel(got_state[0][active], np.asarray(want_S)[active]) < REL
    assert _rel(np.asarray(got_y)[active], np.asarray(want_y)[active]) < REL


# ---- one lane tile a row: the entry N-major, heads side by side on lanes

class _Sizes(sh.Mamba2Sizes):
    """Bare sizes, as the shared mixer's frames read them."""

    def __init__(self, heads, head_dim, state, groups,
                 state_dtype=jnp.float32):
        self.ssm_heads, self.ssm_head_dim = heads, head_dim
        self.ssm_state, self.ssm_groups = state, groups
        self.state_dtype = state_dtype


def _tile_inputs(m, B, seed):
    """The operands of a step at ``m``'s heads, the state ``[B, H, P,
    N]`` in float32 (``state_entry`` lays it as the entry holds it)."""
    rng = np.random.default_rng(seed)
    H_, P_, N_, G_ = m.ssm_heads, m.ssm_head_dim, m.ssm_state, m.ssm_groups
    S, x, Bs, Cs = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                    for shape in ((B, H_, P_, N_), (B, H_, P_),
                                  (B, G_, N_), (B, G_, N_)))
    dt = jnp.asarray(rng.uniform(0.001, 0.2, (B, H_)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.001, 3.0, (B, H_)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(H_,)) + 1.0, jnp.float32)
    return S, (x, Bs, Cs, dt, g, D)


def _nmajor_against_the_oracle(m, S, ops, active):
    """The kernel on the entry as ``state_entry`` lays it, against
    ``ssm_step`` on ``[H, P, N]``; returns nothing, asserts."""
    dtype = jnp.dtype(m.state_dtype)
    entry = sh.state_entry(S, m).astype(dtype)[None]
    assert entry.shape[2:] == sh.state_shape(m)
    got_state, got_y = jax.jit(sh.ssm_step_pallas_nmajor)(
        entry, *ops, jnp.asarray(active))
    assert got_state.shape == entry.shape and got_state.dtype == dtype
    assert got_y.dtype == jnp.float32
    held = sh.state_heads(entry[0], m).astype(jnp.float32)
    want_S, want_y = _oracle(held[None], *ops)
    got_S = np.asarray(sh.state_heads(got_state[0], m).astype(jnp.float32))
    # an inactive lane: not a bit of its state, and y = 0
    assert np.array_equal(np.asarray(got_state[0].astype(jnp.float32))[
        ~active], np.asarray(entry[0].astype(jnp.float32))[~active])
    assert not np.asarray(got_y)[~active].any()
    if active.any():
        assert _rel(np.asarray(got_y)[active],
                    np.asarray(want_y)[active]) < REL
        ulp = 2.0 ** -8 if dtype == jnp.bfloat16 else REL
        assert _rel(got_S[active], np.asarray(want_S)[active]) <= ulp
        assert not np.array_equal(got_S[active], np.asarray(held)[active])


TILE_MASKS = {
    "parked-between": np.array([True, False, True]),
    "all-live": np.array([True, True, True]),
    "no-lane-live": np.array([False, False, False]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("mask", sorted(TILE_MASKS))
@pytest.mark.parametrize("heads_a_block", [2, 4, 8, 16])
def test_heads_of_64_by_128_in_one_group_held_n_major(
        monkeypatch, heads_a_block, mask, dtype):
    """The state-space expert decoder's heads (``[64, 128]``, ALL in
    one group; 16 here, 128 there) as ``ssm_decode`` now steps them:
    the entry ``[H / 2, N, 2 P]``, two heads side by side on lanes, in
    blocks of 1, 2, 4 and all 8 rows of heads; a parked lane between
    two live ones, every lane live, NO lane live (the aliased entry
    comes out as it went in); the state float32 or bfloat16."""
    m = _Sizes(16, 64, 128, 1, dtype)
    assert sh.lane_heads(m) == 2 and sh.state_shape(m) == (8, 128, 128)
    row = 128 * 128 * jnp.dtype(dtype).itemsize      # a row of two heads
    monkeypatch.setattr(sh, "_SSM_BLOCK_BYTES", heads_a_block // 2 * row)
    assert sh.block_heads(8, 1, row) == heads_a_block // 2
    S, ops = _tile_inputs(m, 3, seed=heads_a_block)
    _nmajor_against_the_oracle(m, S, ops, TILE_MASKS[mask])


@pytest.mark.parametrize("head_dim,heads_a_block", [
    (64, 2), (64, 4), (64, 8),          # inside a group, a group, both
    (32, 4), (32, 8), (32, 16),         # four heads a row
    (128, 1), (128, 2), (128, 4)])      # a head a row
def test_two_groups_at_one_lane_tile_a_row_read_their_own_B_and_C(
        monkeypatch, head_dim, heads_a_block):
    """A row of ``k = 128 / P`` heads lies inside ONE group, and a
    block names its groups as the other kernel's does: a block inside a
    group, one group, both; moving the second group's ``B`` and ``C``
    moves that group's heads alone."""
    k = max(1, 128 // head_dim)
    m = _Sizes(4 * k, head_dim, 128, 2)
    assert sh.lane_heads(m) == k
    assert sh.state_shape(m) == (4, 128, k * head_dim)
    row = 128 * k * head_dim * 4
    monkeypatch.setattr(sh, "_SSM_BLOCK_BYTES", heads_a_block // k * row)
    assert sh.block_heads(4, 2, row) == heads_a_block // k
    S, ops = _tile_inputs(m, 3, seed=head_dim + heads_a_block)
    active = np.array([True, False, True])
    _nmajor_against_the_oracle(m, S, ops, active)
    x, Bs, Cs, dt, g, D = ops
    entry = sh.state_entry(S, m)[None]
    s0, y0 = sh.ssm_step_pallas_nmajor(entry, *ops, jnp.asarray(active))
    s1, y1 = sh.ssm_step_pallas_nmajor(
        entry, x, Bs.at[:, 1].mul(2.0), Cs.at[:, 1].mul(-1.0), dt, g, D,
        jnp.asarray(active))
    first, second = slice(0, 2 * k), slice(2 * k, 4 * k)
    s0, s1 = (np.asarray(sh.state_heads(s[0], m)) for s in (s0, s1))
    assert np.array_equal(s0[:, first], s1[:, first])
    assert np.array_equal(np.asarray(y0)[:, first], np.asarray(y1)[:, first])
    assert not np.array_equal(s0[active][:, second], s1[active][:, second])
    assert not np.array_equal(np.asarray(y0)[active][:, second],
                              np.asarray(y1)[active][:, second])


@pytest.mark.parametrize("sizes,want", [
    ((128, 64, 128, 1), (2, (64, 128, 128))),    # granite-4.0-h-small
    ((8, 32, 128, 2), (4, (2, 128, 128))),
    ((8, 128, 128, 2), (1, (8, 128, 128))),
    ((8, 256, 128, 2), (1, (8, 128, 256))),
    ((32, 128, 256, 2), (0, (32, 128, 256))),    # Falcon-H1: two tiles
    ((4, 16, 32, 2), (0, (4, 16, 32))),          # nano: no tile fits
    ((8, 48, 128, 2), (0, (8, 48, 128))),        # heads do not fill lanes
    ((6, 64, 128, 2), (0, (6, 64, 128))),        # a row would span groups
    ((8, 64, 384, 2), (0, (8, 64, 384)))])
def test_one_layout_a_shape_and_the_two_accessors_are_inverses(sizes, want):
    """``lane_heads`` and ``state_shape`` are functions of the sizes
    alone; ``state_entry`` puts head ``r k + i`` of ``[H, P, N]`` at
    lanes ``i P ...`` of row ``r`` with ``N`` on sublanes, and
    ``state_heads`` undoes it to the bit."""
    m = _Sizes(*sizes)
    k, shape = want
    assert sh.lane_heads(m) == k and sh.state_shape(m) == shape
    H_, P_, N_, _ = sizes
    S = jnp.asarray(np.random.default_rng(0).normal(size=(2, H_, P_, N_)),
                    jnp.float32)
    entry = sh.state_entry(S, m)
    assert entry.shape == (2,) + shape
    assert np.array_equal(np.asarray(sh.state_heads(entry, m)),
                          np.asarray(S))
    if k:
        S, entry = np.asarray(S), np.asarray(entry)
        for head, p, n in ((0, 0, 0), (k - 1, P_ - 1, 5), (H_ - 1, 3, 127)):
            assert entry[1, head // k, n, head % k * P_ + p] \
                == S[1, head, p, n]
    else:
        assert entry is S
    spec_state, _ = sh.slot_entries(dataclasses.replace(
        sh.CONFIGS["nano"], ssm_heads=H_, ssm_head_dim=P_, ssm_state=N_,
        ssm_groups=sizes[3]), 3)
    assert spec_state.name == "state3" and tuple(spec_state.shape) == shape


@pytest.mark.parametrize("block,heads,groups,want", [
    (16, 32, 2, 16), (8, 32, 2, 8), (32, 32, 2, 32),   # the cell's
    (16, 4, 2, 4), (16, 6, 2, 6), (4, 12, 4, 3),       # odd groups
    (2, 12, 4, 1), (16, 24, 1, 12), (16, 24, 8, 12)])
def test_a_block_is_whole_groups_or_lies_inside_one(
        monkeypatch, block, heads, groups, want):
    monkeypatch.setattr(sh, "_SSM_BLOCK_BYTES", block * HEAD)
    hb = sh._block_heads(heads, groups, HEAD)
    per = heads // groups
    assert hb == want and heads % hb == 0
    assert hb % per == 0 or per % hb == 0
    assert hb <= per or groups % (hb // per) == 0


@pytest.mark.parametrize("heads_a_block", [2, 4, 8])
def test_a_group_of_heads_reads_its_own_B_and_C(monkeypatch,
                                                heads_a_block):
    """Moving one group's ``B`` and ``C`` moves that group's heads'
    state and ``y`` and leaves the other group's bits alone."""
    monkeypatch.setattr(sh, "_SSM_BLOCK_BYTES", heads_a_block * HEAD)
    state, x, Bs, Cs, dt, g, D = _inputs(3, seed=7)
    active = jnp.ones((3,), bool)
    s0, y0 = sh._ssm_step_pallas(state, x, Bs, Cs, dt, g, D, active)
    s1, y1 = sh._ssm_step_pallas(state, x, Bs.at[:, 1].mul(2.0),
                                 Cs.at[:, 1].mul(-1.0), dt, g, D, active)
    first, second = slice(0, H // G), slice(H // G, H)
    assert np.array_equal(np.asarray(s0)[0][:, first],
                          np.asarray(s1)[0][:, first])
    assert np.array_equal(np.asarray(y0)[:, first], np.asarray(y1)[:, first])
    assert not np.array_equal(np.asarray(s0)[0][:, second],
                              np.asarray(s1)[0][:, second])
    assert not np.array_equal(np.asarray(y0)[:, second],
                              np.asarray(y1)[:, second])
    want_S, want_y = _oracle(state, x, Bs.at[:, 1].mul(2.0),
                             Cs.at[:, 1].mul(-1.0), dt, g, D)
    assert _rel(s1[0], want_S) < REL and _rel(y1, want_y) < REL


def test_y_is_taken_from_the_state_before_the_step():
    """``y = a (S C) + dt x (B . C) + D x``: with ``B`` zero the state
    only decays and ``y`` must still see the OLD state times ``a``,
    which is the NEW state's sum; with ``C`` zero ``y`` is the skip."""
    state, x, Bs, Cs, dt, g, D = _inputs(2, seed=3)
    active = jnp.ones((2,), bool)
    s, y = sh._ssm_step_pallas(state, x, 0.0 * Bs, Cs, dt, g, D, active)
    per = H // G
    after = np.einsum("bhpn,bhn->bhp", np.asarray(s)[0],
                      np.repeat(np.asarray(Cs), per, axis=1))
    skip = np.asarray(D)[:, None] * np.asarray(x)
    assert _rel(np.asarray(y), after + skip) < REL
    _, y = sh._ssm_step_pallas(state, x, Bs, 0.0 * Cs, dt, g, D, active)
    assert np.array_equal(np.asarray(y), skip)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_state_keeps_its_dtype_and_the_arithmetic_is_float32(dtype):
    """A state held in bfloat16 (``state_dtype``) is widened in the
    kernel, as the XLA path widens it, and rounded once on the way
    back."""
    state, *ops = _inputs(3, seed=11)
    state = state.astype(dtype)
    active = jnp.asarray([True, False, True])
    got_state, got_y = sh._ssm_step_pallas(state, *ops, active)
    want_S, want_y = _oracle(state, *ops)
    assert got_state.dtype == dtype and got_y.dtype == jnp.float32
    live = np.asarray(active)
    assert _rel(np.asarray(got_y)[live], np.asarray(want_y)[live]) < REL
    ulp = 2.0 ** -8 if dtype == jnp.bfloat16 else REL
    assert _rel(got_state[0].astype(jnp.float32)[live],
                np.asarray(want_S)[live]) <= ulp
    assert np.array_equal(np.asarray(got_state[0, 1], np.float32),
                          np.asarray(state[0, 1], np.float32))


def _step_pair(cfg, monkeypatch):
    """One decode step over three prefilled lanes with the kernel and
    with the fallback: ``((logits, cache), (logits, cache), held)``."""
    params = sh.init_params(jax.random.PRNGKey(0), cfg)
    slots, ps, max_pages = 3, 4, 8
    cache = sh.init_paged_cache(cfg, slots, slots * max_pages, ps)
    pt = np.arange(slots * max_pages, dtype=np.int32).reshape(slots, -1)
    rng = np.random.default_rng(5)
    prefill = sh.jit_prefill_into_slot_paged(cfg, ps)
    for slot in range(slots):
        padded = np.zeros((1, 16), np.int32)
        padded[0, :9 + slot] = rng.integers(0, cfg.vocab_size, 9 + slot)
        _, cache, _ = prefill(params, cache, padded, np.int32(9 + slot),
                              np.int32(0), pt[slot],
                              np.int32(serving.PT_SENTINEL), np.int32(slot),
                              jax.random.PRNGKey(0))
    held = jax.tree_util.tree_map(np.asarray, cache)
    active = np.array([True, False, True])
    out = []
    for fused in (True, False):
        monkeypatch.setattr(sh, "_state_kernel",
                            lambda cfg, fused=fused: fused)
        step = jax.jit(functools.partial(sh._slot_decode_step_paged,
                                         cfg=cfg, page_size=ps))
        logits, after, counts = step(params, dict(cache),
                                     jnp.asarray([5, 7, 9]), active,
                                     jnp.asarray(pt))
        assert int(counts[0]) == 2
        out.append((np.asarray(logits),
                    jax.tree_util.tree_map(np.asarray, after)))
    return out[0], out[1], held


def test_the_step_with_the_kernel_stays_by_the_step_with_the_fallback(
        monkeypatch):
    """In float32 (nothing rounds what the two sum differently) the
    live lanes' logits and the whole cache agree to :data:`REL`-sized
    bounds, and the parked lane's state and tail are the bits that
    went in, on both paths, in every layer."""
    cfg = dataclasses.replace(sh.CONFIGS["nano"], dtype=jnp.float32,
                              param_dtype=jnp.float32)
    (lg_k, c_k), (lg_x, c_x), held = _step_pair(cfg, monkeypatch)
    assert _rel(lg_k[[0, 2]], lg_x[[0, 2]]) < 1e-4
    assert sorted(c_k) == sorted(c_x) == sorted(held)
    for name in c_k:
        assert c_k[name].shape == held[name].shape
        assert c_k[name].dtype == held[name].dtype
        if name != "pos":
            assert _rel(c_k[name], c_x[name]) < 1e-5, name
    for c in (c_k, c_x):
        for l in range(cfg.n_layer):
            for name in (sh.slot_entry("state", l), sh.slot_entry("conv", l)):
                assert np.array_equal(c[name][:, 1], held[name][:, 1])
                assert not np.array_equal(c[name][:, 0], held[name][:, 0])
    assert list(c_k["pos"]) == list(c_x["pos"])


def test_the_choice_is_made_from_what_the_program_can_see(monkeypatch):
    """Interpreted (here) any width is addressable; compiled for a TPU
    a head's state must be whole tiles of the state dtype: with TWO
    lane tiles a row or more (``N`` of 256) as the entry ``[H, P, N]``
    lies (``P`` of 8 sublanes in float32 and 16 in bfloat16), with ONE
    lane tile a row (``N`` of 128) as the ``N``-major entry lies, heads
    side by side filling the lanes (``ssm_step_pallas_nmajor``: PERF.md
    section 6, PR 56); any other shape takes ``_ssm_step``: the
    description says which, the program holds a ``pallas_call`` or
    none, and no knob has a say."""
    from ray_tpu._private import chip

    nano = sh.CONFIGS["nano"]
    wide = dataclasses.replace(nano, ssm_head_dim=8, ssm_state=256)
    assert sh.decode_attention_fused(nano, 4)
    assert sh._state_kernel(nano)
    monkeypatch.setattr(chip, "pallas_interpret", lambda: False)
    # neither kernel: heads of 16 (the attention's) and a state of 32
    assert not sh.decode_attention_fused(nano, 4)
    assert not sh._state_kernel(nano)
    assert not sh._state_kernel(dataclasses.replace(wide, ssm_state=192))
    # one lane tile a row: the kernel of the N-major entry wherever the
    # heads of a group fill the lanes side by side, in either dtype
    # (sixteen heads of 8 would, and a group of ``wide`` has two)
    tile = dataclasses.replace(wide, ssm_state=128, ssm_head_dim=64)
    assert sh.lane_heads(tile) == 2 and sh._state_kernel(tile)
    assert sh._state_kernel(dataclasses.replace(
        tile, state_dtype=jnp.bfloat16))
    assert sh._state_kernel(dataclasses.replace(
        tile, ssm_heads=128, ssm_groups=1))          # granite-4.0-h-small
    assert sh._state_kernel(dataclasses.replace(tile, ssm_head_dim=128))
    for off in (dict(ssm_head_dim=8), dict(ssm_head_dim=48),
                dict(ssm_heads=6), dict(ssm_state=384)):
        cfg = dataclasses.replace(tile, **off)
        assert not sh.lane_heads(cfg) and not sh._state_kernel(cfg), off
    assert not sh._state_kernel(dataclasses.replace(wide, ssm_head_dim=12))
    assert not sh._state_kernel(dataclasses.replace(
        wide, state_dtype=jnp.bfloat16))
    assert sh._state_kernel(dataclasses.replace(
        wide, ssm_head_dim=16, state_dtype=jnp.bfloat16))
    assert sh._state_kernel(wide)
    # the recurrence's kernel alone makes the program's answer true
    assert not kda_moe.gqa_kernel(wide.n_kv_head, wide.head_dim,
                                  wide.dtype, 16)
    assert sh.decode_attention_fused(wide, 16)
    assert sh.decode_attention_fused(wide, 16, "gather")
    assert sh.ATTN_KERNELS == ("gather",)
    with pytest.raises(ValueError, match="attn_kernel must be one of"):
        sh.jit_decode_chunk_slots_paged(nano, 4, 4, attn_kernel="pallas")

    # what the description says is what the traced program holds
    def held(cfg):
        params = jax.eval_shape(
            lambda: sh.init_params(jax.random.PRNGKey(0), cfg))
        cache = jax.eval_shape(lambda: sh.init_paged_cache(cfg, 2, 8, 4))
        S = jax.ShapeDtypeStruct
        return str(jax.make_jaxpr(functools.partial(
            sh._slot_decode_step_paged, cfg=cfg, page_size=4))(
            params, cache, S((2,), jnp.int32), S((2,), jnp.bool_),
            S((2, 4), jnp.int32))).count("pallas_call")

    assert held(nano) == 0                   # off the tile: _ssm_step
    assert held(wide) == wide.n_layer        # the recurrence's, a layer
    assert held(tile) == tile.n_layer        # the N-major entry's
    monkeypatch.undo()
    assert held(nano) == 2 * nano.n_layer    # interpreted: both kernels


def test_the_engine_reports_the_kernel_and_counts_the_lanes_it_moved():
    cfg = sh.CONFIGS["nano"]
    eng = DecodeEngine(sh.init_params(jax.random.PRNGKey(0), cfg), cfg,
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
        # one live lane a step, whatever the layers
        assert st["state_lanes_sum"] == st["dispatches"] * 4
    finally:
        eng.shutdown()
