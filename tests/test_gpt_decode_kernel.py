"""Fused paged-attention kernel + int8 quantized KV cache (ISSUE 16).

The contract under test (ROADMAP D10, restated by PR 35):

- ``attn_kernel="pallas"`` (Pallas ``pallas_call`` on TPU, interpret
  mode on CPU — tier-1 exercises the REAL kernel body either way) reads
  each slot's live pages once, in one softmax pass, and rounds its
  probabilities before the division by their sum where the XLA gather
  reference rounds them after it. So the two agree to a WRITTEN BOUND,
  ``gpt_decode.ATTN_KERNEL_ULPS`` bf16 ulps of the largest output, not
  to the bit — across page sizes, table widths, lengths, out-of-order
  and sentinel-padded tables, fp and int8 pages, heads sharded — and
  nothing outside a slot's live positions can move an output bit,
  ``inf`` and ``NaN`` included. Streams are judged as the benchmark
  judges them: every token within the margin of the reference path's
  best logit on the same history (seeded sampling: of its best
  PERTURBED logit). Identity is asserted only where it holds by
  construction: the same engine twice, the prefill's first token.
- The kernel's work does not scale with the table's width: its grid is
  one step a slot, and K and V are not blocked operands.
- ``kv_dtype="int8"`` (per-page-per-head scales, quantize on scatter /
  dequantize at attention) bounds its round-trip error by one quantum
  (``1/127`` relative to the page's absmax) and documents a temp-0
  divergence RATE vs fp rather than pretending bit-identity: measured
  ~0.2 of streams diverge somewhere on random nano weights, asserted
  here under a loose 0.5 ceiling, with the FIRST token exact (the
  prefill's own forward runs in fp).
- Both knobs preserve the ``len(prompt_buckets) + k`` compiled-program
  budget and the handoff plane (int8 ships codes + scales; the digest
  covers both; any layout mismatch degrades to the counted local
  re-prefill).
"""
import threading

import numpy as np
import pytest


def _drain(lane):
    from ray_tpu.serve.batching import _EngineStream

    return np.concatenate(list(_EngineStream(lane)))


@pytest.fixture(scope="module")
def nano():
    from ray_tpu.models import gpt

    return gpt.CONFIGS["nano"]


@pytest.fixture(scope="module")
def nano_params(nano):
    import jax

    from ray_tpu.models import gpt

    return gpt.init_params(jax.random.PRNGKey(0), nano)


def _make(nano, nano_params, **kw):
    from ray_tpu.serve.engine import DecodeEngine

    kw.setdefault("slots", 2)
    kw.setdefault("chunk", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_buckets", (8, 16))
    kw.setdefault("page_size", 8)
    return DecodeEngine(nano_params, nano, **kw)


def _drain_concurrent(eng, prompts, max_news, seeds=None):
    outs = {}

    def consume(i):
        kw = {"seed": seeds[i]} if seeds else {}
        outs[i] = np.concatenate(
            list(eng.stream(prompts[i], max_news[i], **kw)))

    threads = [threading.Thread(target=consume, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outs


def _prefix_prompts(nano, rng, n_fresh=2):
    """A shared 12-token system prompt with fresh 4-token tails: the
    second admission hits the prefix cache mid-page (12 % 8 != 0) and
    forks the partial page copy-on-write."""
    sysp = rng.integers(0, nano.vocab_size, (12,)).astype(np.int32)
    out = []
    for _ in range(n_fresh):
        tail = rng.integers(0, nano.vocab_size, (4,)).astype(np.int32)
        out.append(np.concatenate([sysp, tail]))
    return out


# --------------------------------------------------- kernel vs reference
#: The benchmark's rule for a served token (``PERF.md`` section 2): it
#: lies within 2 x ``logits_rel_tol`` x max|ref| of the reference's best
#: logit — a token chosen from logits off by e lies at most 2e below.
LOGITS_REL_TOL = 0.025


def _ulps(got, ref):
    """max|got - ref| in bf16 ulps of the largest reference output."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.abs(got - ref).max() / (2.0 ** -8 * np.abs(ref).max()))


def _pool(rng, n_pages, ps, H, hd, dtype, quant):
    import jax.numpy as jnp

    shape = (n_pages, ps, H, hd)
    if quant:
        return (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                jnp.asarray(rng.uniform(.005, .03, (n_pages, H)),
                            jnp.float32),
                jnp.asarray(rng.uniform(.005, .03, (n_pages, H)),
                            jnp.float32))
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype), None, None)


def _poison(kc, vc, ks, vs, pt, pos, ps, bad):
    """The pool with ``bad`` in every page no table maps and in every
    position past a lane's ``pos`` inside its last page — codes at
    their extreme and the scale ``bad`` where the pool is int8."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode

    live = {int(p) for p in np.asarray(pt).ravel()
            if p != gpt_decode.PT_SENTINEL}
    dead = np.array([p not in live for p in range(kc.shape[0])])
    k2, v2 = np.array(kc, np.float32), np.array(vc, np.float32)
    fill = 127 if ks is not None else bad
    k2[dead] = fill
    v2[dead] = fill
    for row, last in zip(np.asarray(pt), np.asarray(pos)):
        if row[0] != gpt_decode.PT_SENTINEL:
            k2[row[last // ps], last % ps + 1:] = fill
            v2[row[last // ps], last % ps + 1:] = fill
    if ks is not None:
        ks = jnp.where(dead[:, None], bad, ks)
        vs = jnp.where(dead[:, None], bad, vs)
    return jnp.asarray(k2, kc.dtype), jnp.asarray(v2, vc.dtype), ks, vs


def test_paged_attention_matches_gather_direct(nano, nano_params):
    """Direct kernel-vs-reference on a hand-built pool: random pages,
    page tables with SENTINEL padding and out-of-order mappings, per
    -slot lengths that end mid-page. The fused kernel agrees with the
    gather reference to the written bound — and ``inf`` and ``NaN`` in
    every page the tables never map and past a slot's pos inside its
    last page do not move an output bit (the length mask covers V's
    side too, and an unmapped page is never fetched)."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode

    H, hd, ps, n_pages, max_pages, B = nano.n_head, nano.head_dim, 8, \
        16, 4, 3
    rng = np.random.default_rng(21)
    kc, vc, _, _ = _pool(rng, n_pages, ps, H, hd, nano.dtype, False)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), nano.dtype)
    pt = np.full((B, max_pages), gpt_decode.PT_SENTINEL, np.int32)
    pt[0, :2] = [5, 3]            # out of order, 2 pages + sentinels
    pt[1, :4] = [7, 0, 9, 2]      # full table
    pt[2, :1] = [11]              # single page, ends mid-page
    pos = jnp.asarray([12, 30, 4], jnp.int32)   # mid-page lengths
    ref = gpt_decode.paged_attention(q, kc, vc, jnp.asarray(pt), pos,
                                     page_size=ps, kernel="gather")
    out = gpt_decode.paged_attention(q, kc, vc, jnp.asarray(pt), pos,
                                     page_size=ps, kernel="pallas")
    assert _ulps(out, ref) <= gpt_decode.ATTN_KERNEL_ULPS
    for bad in (1e4, np.inf, np.nan):
        kc2, vc2, _, _ = _poison(kc, vc, None, None, pt, pos, ps, bad)
        out2 = gpt_decode.paged_attention(
            q, kc2, vc2, jnp.asarray(pt), pos, page_size=ps,
            kernel="pallas")
        assert np.array_equal(np.asarray(out2, np.float32),
                              np.asarray(out, np.float32)), bad


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
@pytest.mark.parametrize("ps,max_pages", [
    (8, 4), (16, 4), (8, 128), (16, 128), (8, 1), (64, 1)],
    ids=lambda v: str(v))
def test_kernel_adapts_to_pool_shape(nano, ps, max_pages, kv_dtype):
    """What the kernel's blocks adapt to — ``page_size`` 8 / 16
    / a lane's whole ``max_len`` (64, one page a lane), tables 1 / 4 /
    128 columns wide — over lanes at ``pos`` 0, mid-page, page-exact
    and full, an out-of-order table, and a lane whose row is all
    sentinels (finite zeros): within the written bound of the gather
    path, and not a bit moved by ``inf`` or ``NaN`` in every unmapped
    page and past ``pos`` inside a live page."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode as gd

    H, hd = nano.n_head, nano.head_dim
    quant = kv_dtype == "int8"
    rng = np.random.default_rng(41 + ps + max_pages)
    full = max_pages * ps - 1
    lanes = [0, min(ps // 2, full), ps - 1, full,       # the four pos
             min(ps + 2, full)]                         # out of order
    B = len(lanes) + 1                                  # + all sentinel
    n_pages = sum(p // ps + 1 for p in lanes) + 5
    perm, off = rng.permutation(n_pages), 0
    pt = np.full((B, max_pages), gd.PT_SENTINEL, np.int32)
    for b, p in enumerate(lanes):
        n = p // ps + 1
        pt[b, :n] = perm[off:off + n] if b < 4 else \
            np.sort(perm[off:off + n])[::-1]
        off += n
    pos = jnp.asarray(lanes + [0], jnp.int32)
    kc, vc, ks, vs = _pool(rng, n_pages, ps, H, hd, nano.dtype, quant)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), nano.dtype)

    def run(kernel, kc, vc, ks, vs):
        return np.asarray(gd.paged_attention(
            q, kc, vc, jnp.asarray(pt), pos, page_size=ps, kernel=kernel,
            ks=ks, vs=vs), np.float32)

    out = run("pallas", kc, vc, ks, vs)
    ref = run("gather", kc, vc, ks, vs)
    assert np.isfinite(out).all() and (out[-1] == 0).all()
    assert _ulps(out[:-1], ref[:-1]) <= gd.ATTN_KERNEL_ULPS
    for bad in (np.inf, np.nan):
        assert np.array_equal(
            run("pallas", *_poison(kc, vc, ks, vs, pt, pos, ps, bad)),
            out), bad


#: Tokens of a block in the cases below (the kernel's own rule gives a
#: block of 256 at two heads: a table of 128 positions would be one).
BLOCK = 32
#: ``pos`` of each lane of a call, None for a row of sentinels: what the
#: lanes' blocks as ONE stream have to get right. A lane that ends on a
#: block's last token has no partial block; one of three blocks refills
#: its own ring; and empty lanes first, between and last are skipped by
#: the fetch that runs ahead (the ring is filled once a call, from the
#: first lane that has a block).
STREAMS = {
    "ends-on-a-block": [BLOCK - 1, 2 * BLOCK - 1, 3 * BLOCK - 1],
    "three-blocks": [2 * BLOCK + 5, 3 * BLOCK - 2, 2 * BLOCK],
    "first-lanes-empty": [None, None, BLOCK + 3, None, 5, 2 * BLOCK, None],
}


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
@pytest.mark.parametrize("ps", [16, 64, 96])
@pytest.mark.parametrize("stream", sorted(STREAMS))
def test_kernel_reads_the_lanes_blocks_as_one_stream(nano, monkeypatch,
                                                     stream, ps, kv_dtype):
    """The cases of :data:`STREAMS` at blocks of :data:`BLOCK` tokens,
    two pages of 16, or a half of a page of 64 and a third of one of
    96 (a page larger than a block is read in parts, each a block and
    a copy): within the written bound of the gather on every
    live lane, zeros on every empty one, and not a bit moved by ``inf``
    or ``NaN`` in the pages no table maps and past ``pos``."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode as gd, kda_moe

    monkeypatch.setattr(kda_moe, "_GQA_BLOCK_TOKENS", BLOCK)
    assert kda_moe._gqa_block(nano.n_head, ps) == {
        16: (2, 1), 64: (1, 2), 96: (1, 3)}[ps]
    H, hd, max_pages = nano.n_head, nano.head_dim, 8
    quant = kv_dtype == "int8"
    lanes = STREAMS[stream]
    rng = np.random.default_rng(61 + len(lanes))
    n_pages = sum(p // ps + 1 for p in lanes if p is not None) + 4
    perm, off = rng.permutation(n_pages), 0
    pt = np.full((len(lanes), max_pages), gd.PT_SENTINEL, np.int32)
    for b, p in enumerate(lanes):
        if p is not None:
            pt[b, :p // ps + 1] = perm[off:off + p // ps + 1]
            off += p // ps + 1
    live = np.array([p is not None for p in lanes])
    pos = jnp.asarray([p or 0 for p in lanes], jnp.int32)
    kc, vc, ks, vs = _pool(rng, n_pages, ps, H, hd, nano.dtype, quant)
    q = jnp.asarray(rng.standard_normal((len(lanes), 1, H, hd)),
                    nano.dtype)

    def run(kernel, kc, vc, ks, vs):
        return np.asarray(gd.paged_attention(
            q, kc, vc, jnp.asarray(pt), pos, page_size=ps, kernel=kernel,
            ks=ks, vs=vs), np.float32)

    out = run("pallas", kc, vc, ks, vs)
    ref = run("gather", kc, vc, ks, vs)
    assert np.isfinite(out).all() and (out[~live] == 0).all()
    assert _ulps(out[live], ref[live]) <= gd.ATTN_KERNEL_ULPS
    for bad in (np.inf, np.nan):
        assert np.array_equal(
            run("pallas", *_poison(kc, vc, ks, vs, pt, pos, ps, bad)),
            out), bad


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_kernel_under_sharded_heads(nano, kv_dtype):
    """``H`` sharded by 2, as ``tp=2`` runs it (under ``shard_map``,
    unchecked): each device's kernel sees one head, and the result is
    the unsharded kernel's to the bit — a head's arithmetic does not
    depend on how many heads share the page."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ray_tpu._private.jax_compat import decode_mesh, shard_map
    from ray_tpu.models import gpt_decode as gd

    H, hd, ps, B = nano.n_head, nano.head_dim, 8, 3
    quant = kv_dtype == "int8"
    rng = np.random.default_rng(43)
    kc, vc, ks, vs = _pool(rng, 12, ps, H, hd, nano.dtype, quant)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), nano.dtype)
    pt = np.full((B, 4), gd.PT_SENTINEL, np.int32)
    pt[0, :3] = [4, 9, 1]
    pt[2, :1] = [7]
    pt, pos = jnp.asarray(pt), jnp.asarray([20, 0, 7], jnp.int32)
    heads = P(None, None, "tp")
    scales = (P(None, "tp"),) * 2 if quant else ()

    def attend(q, kc, vc, pt, pos, *sc):
        return gd.paged_attention(q, kc, vc, pt, pos, page_size=ps,
                                  kernel="pallas", ks=sc[0] if sc else None,
                                  vs=sc[1] if sc else None)

    args = (q, kc, vc, pt, pos) + ((ks, vs) if quant else ())
    sharded = jax.jit(shard_map(
        attend, mesh=decode_mesh(2),
        in_specs=(heads, heads, heads, P(), P()) + scales,
        out_specs=heads, check_vma=False))(*args)
    assert np.array_equal(np.asarray(sharded, np.float32),
                          np.asarray(attend(*args), np.float32))


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_kernel_work_does_not_scale_with_table_width(nano, kv_dtype):
    """The guard against dead grid steps coming back: from the jaxpr
    of ``paged_attention(kernel="pallas")``, the ``pallas_call``'s grid
    is one step a slot — no ``max_pages`` axis, no factor 2, the same
    at 4 and at 128 columns — and K and V are whole-pool operands left
    in place (memory space ``any``), not blocks of a per-column
    ``BlockSpec``: the kernel fetches what a slot's own length asks
    for."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode as gd

    H, hd, ps, n_pages, B = nano.n_head, nano.head_dim, 8, 16, 3
    quant = kv_dtype == "int8"
    kc, vc, ks, vs = _pool(np.random.default_rng(0), n_pages, ps, H, hd,
                           nano.dtype, quant)
    q = jnp.zeros((B, 1, H, hd), nano.dtype)
    grids = []
    for max_pages in (4, 128):
        pt = jnp.zeros((B, max_pages), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda q, kc, vc, pt, pos: gd.paged_attention(
            q, kc, vc, pt, pos, page_size=ps, kernel="pallas", ks=ks,
            vs=vs))(q, kc, vc, pt, jnp.zeros((B,), jnp.int32))
        (call,) = _eqns(jaxpr.jaxpr, "pallas_call")
        gm = call.params["grid_mapping"]
        grids.append(tuple(gm.grid))
        # the whole pool, viewed as the kernel takes a page: its rows
        # as they lie, ``(token, head)`` (a reshape that moves no byte)
        view = (n_pages, ps * H, hd)
        pools = [bm.transformed_block_aval for bm in gm.block_mappings
                 if bm.array_aval.shape == view]
        assert len(pools) == 2                          # K and V
        for block in pools:
            assert str(block.memory_space) == "any", block
            assert block.shape == view, block
        assert "paged_attention" in str(call.source_info.name_stack)
    assert grids == [(B,), (B,)]


def _judged_case(nano, nano_params, kv_dtype, prompts, ps=8):
    """A pool with ``prompts`` prefilled into it by the engine's own
    prefill program, one lane each, contiguous pages. Returns ``(cache,
    pt, first)``: ``first`` is each lane's first token (temperature
    0)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode as gd

    B, max_pages = len(prompts), 64 // ps
    cache = gd.init_paged_cache(nano, B, B * max_pages, ps, kv_dtype)
    pt = np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages)
    first = []
    for b, prompt in enumerate(prompts):
        tokens = np.zeros((1, 16), np.int32)
        tokens[0, :len(prompt)] = prompt
        tok, cache, _ = gd.prefill_into_slot_paged(
            nano_params, cache, jnp.asarray(tokens),
            jnp.asarray(len(prompt)), jnp.asarray(0), jnp.asarray(pt[b]),
            jnp.asarray(gd.PT_SENTINEL), jnp.asarray(b),
            jax.random.PRNGKey(0), cfg=nano, page_size=ps,
            kv_dtype=kv_dtype)
        first.append(int(np.asarray(tok).ravel()[0]))
    return cache, jnp.asarray(pt), jnp.asarray(first, jnp.int32)


def _reference_logits(nano, nano_params, kv_dtype, cache, pt, first,
                      forced, ps=8):
    """The REFERENCE path's logits on a given history: ``forced [B,
    k]`` is fed token by token through the gather path's decode step
    from ``cache``; ``out[:, j]`` are the logits that choose
    ``forced[:, j]``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode as gd

    step = jax.jit(lambda c, tok: gd._slot_decode_step_paged(
        nano_params, c, tok, jnp.ones(tok.shape, bool), pt, nano, ps,
        kv_dtype, "gather"))
    out, tok = [], first
    for j in range(forced.shape[1]):
        logits, cache = step(cache, tok)
        out.append(np.asarray(logits, np.float32))
        tok = jnp.asarray(forced[:, j], jnp.int32)
    return np.stack(out, axis=1)


def _gaps(ref, tokens, noise=None):
    """How far below the reference's best (perturbed) logit each token
    lies, in units of the benchmark's margin; <= 1 passes."""
    ref = ref + (0.0 if noise is None else noise)
    margin = 2 * LOGITS_REL_TOL * np.abs(ref).max()
    chosen = np.take_along_axis(ref, np.asarray(tokens)[..., None],
                                axis=-1)[..., 0]
    return (ref.max(axis=-1) - chosen) / margin


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_kernel_token_identity_greedy(nano, nano_params, kv_dtype):
    """Kernel on at temperature 0, on BOTH cache layouts, judged as the
    benchmark judges a served stream: every token of every lane of the
    kernel's program lies within the margin of the REFERENCE path's
    best logit on the same history (the gather step, teacher-forced
    through the kernel's own tokens on the same cache bytes); the
    control — another lane's tokens against these logits — fails. And
    the engine serves through it: mixed prompt lengths
    (sentinel-padded tables), a shared prefix hit that forks mid-page
    (COW), concurrent slots; whole streams with ids in the table, the
    FIRST token of each the gather engine's (the prefill runs no
    kernel: identity by construction)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode as gd

    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, nano.vocab_size, (n,)).astype(np.int32)
               for n in (5, 11, 16)] + _prefix_prompts(nano, rng)
    k = 12
    cache, pt, first = _judged_case(nano, nano_params, kv_dtype, prompts)
    B = len(prompts)
    toks, _, _, _ = gd.decode_chunk_slots_paged(
        nano_params, cache, first, jnp.zeros((B, 2), jnp.uint32),
        jnp.ones((B,), bool), pt, cfg=nano, k=k, page_size=8,
        kv_dtype=kv_dtype, attn_kernel="pallas")
    toks = np.asarray(toks)
    ref = _reference_logits(nano, nano_params, kv_dtype, cache, pt,
                            first, toks)
    assert _gaps(ref, toks).max() <= 1.0, _gaps(ref, toks).max()
    assert _gaps(ref, np.roll(toks, 1, axis=0)).max() > 1.0   # control

    ref_eng = _make(nano, nano_params, prefix_cache=True,
                    prompt_buckets=(8, 16), kv_dtype=kv_dtype,
                    attn_kernel="gather")
    ker = _make(nano, nano_params, prefix_cache=True,
                prompt_buckets=(8, 16), kv_dtype=kv_dtype,
                attn_kernel="pallas")
    try:
        max_news = [9, 7, 12, 8, 8]
        of = _drain_concurrent(ref_eng, prompts, max_news)
        ok = _drain_concurrent(ker, prompts, max_news)
        for i in range(len(prompts)):
            assert ok[i].shape == (max_news[i],)
            assert ((ok[i] >= 0) & (ok[i] < nano.vocab_size)).all()
            assert of[i][0] == ok[i][0], (i, of[i], ok[i])
        st = ker.stats()
        assert st["attn_kernel"] == "pallas"
        assert st["attn_kernel_dispatches"] > 0
        assert ref_eng.stats()["attn_kernel_dispatches"] == 0
    finally:
        ref_eng.shutdown()
        ker.shutdown()


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_kernel_token_identity_temperature(nano, nano_params, kv_dtype):
    """Seeded sampling (temp 1.0). A sampled token is the argmax of
    ``logits / T`` plus the Gumbel noise of the lane's PRNG chain, so
    it is judged like a greedy one: every token the kernel's program
    samples lies within the margin of the reference path's best
    PERTURBED logit on the same history (the chain is rebuilt here, and
    checked: it reproduces the gather program's own tokens exactly).
    By construction: the same seed gives the same stream twice through
    the kernel, a different seed diverges (the sampler is live), and
    the first token is the gather engine's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode as gd

    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, nano.vocab_size, (n,)).astype(np.int32)
               for n in (8, 13)]
    k, B = 10, 2
    cache, pt, first = _judged_case(nano, nano_params, kv_dtype, prompts)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray([7, 11]))
    noise, chain = [], keys
    for _ in range(k):
        split = jax.vmap(jax.random.split)(chain)
        chain = split[:, 0]
        noise.append(np.asarray(jax.vmap(lambda s: jax.random.gumbel(
            s, (nano.vocab_size,), jnp.float32))(split[:, 1])))
    noise = np.stack(noise, axis=1)                     # [B, k, V]
    sampled = {}
    for kernel in gd.ATTN_KERNELS:
        toks, _, _, _ = gd.decode_chunk_slots_paged(
            nano_params, cache, first, keys, jnp.ones((B,), bool), pt,
            cfg=nano, k=k, page_size=8, temperature=1.0,
            kv_dtype=kv_dtype, attn_kernel=kernel)
        sampled[kernel] = np.asarray(toks)
    own = _reference_logits(nano, nano_params, kv_dtype, cache, pt, first,
                            sampled["gather"])
    assert ((own + noise).argmax(-1) == sampled["gather"]).all()
    ref = _reference_logits(nano, nano_params, kv_dtype, cache, pt, first,
                            sampled["pallas"])
    assert _gaps(ref, sampled["pallas"], noise).max() <= 1.0
    assert _gaps(ref, sampled["pallas"][::-1], noise).max() > 1.0

    ref_eng = _make(nano, nano_params, temperature=1.0,
                    prefix_cache=False, kv_dtype=kv_dtype,
                    attn_kernel="gather")
    ker = _make(nano, nano_params, temperature=1.0, prefix_cache=False,
                kv_dtype=kv_dtype, attn_kernel="pallas")
    try:
        max_news = [8, 10]
        seeds = [7, 11]
        of = _drain_concurrent(ref_eng, prompts, max_news, seeds)
        ok = _drain_concurrent(ker, prompts, max_news, seeds)
        again = _drain_concurrent(ker, prompts, max_news, seeds)
        for i in range(2):
            assert (again[i] == ok[i]).all(), (i, again[i], ok[i])
            assert of[i][0] == ok[i][0], (i, of[i], ok[i])
        other = np.concatenate(list(ker.stream(prompts[0], 8, seed=8)))
        assert not (other == ok[0]).all()
    finally:
        ref_eng.shutdown()
        ker.shutdown()


# ------------------------------------------------------------ int8 layout
def test_int8_roundtrip_error_bound(nano):
    """Quantize-on-scatter round trip: one page written through
    ``_merge_span_int8`` dequantizes back within ONE quantum — the
    per-page-per-head scale is absmax/127, so |x - deq(q(x))| <=
    scale/2 elementwise, i.e. rel err <= 1/127 of the page-head
    absmax. Codes past the written span must be canonical zeros (page
    bytes are a pure function of held tokens — what the handoff digest
    relies on)."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode

    H, hd, ps = nano.n_head, nano.head_dim, 8
    rng = np.random.default_rng(5)
    vals = rng.standard_normal((1, 6, H, hd)).astype(np.float32)
    codes = jnp.zeros((4, ps, H, hd), jnp.int8)     # per-layer pool
    scales = jnp.zeros((4, H), jnp.float32)
    pt = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    c2, s2 = gpt_decode._merge_span_int8(
        codes, scales, jnp.asarray(vals), pt, jnp.asarray([0]),
        jnp.asarray(6), jnp.asarray([True]), ps)
    deq = np.asarray(c2, np.float32) * \
        np.asarray(s2)[:, None, :, None]
    absmax = np.abs(vals[0, :6]).max(axis=(0, 2))       # per head
    err = np.abs(deq[0, :6] - vals[0, :6])
    assert (err <= absmax[None, :, None] / 127.0 + 1e-7).all()
    # Canonical zeros past the span, in codes AND untouched pages.
    assert (np.asarray(c2)[0, 6:] == 0).all()
    assert (np.asarray(c2)[1:] == 0).all()
    assert (np.asarray(s2)[1:] == 0).all()


def test_int8_divergence_rate_documented(nano, nano_params):
    """fp vs int8 at temperature 0 on the SAME weights: the FIRST
    token of every stream is exact (prefill's forward runs in fp; only
    the CACHE is quantized), and the stream-divergence rate sits under
    the documented 0.5 ceiling (measured ~0.2 on random nano weights —
    real checkpoints with peaked logits sit far lower)."""
    fp = _make(nano, nano_params, slots=2, prefix_cache=False,
               kv_dtype="fp")
    q8 = _make(nano, nano_params, slots=2, prefix_cache=False,
               kv_dtype="int8")
    try:
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, nano.vocab_size,
                                (int(n),)).astype(np.int32)
                   for n in rng.integers(5, 16, 10)]
        max_news = [8] * len(prompts)
        of = _drain_concurrent(fp, prompts, max_news)
        oq = _drain_concurrent(q8, prompts, max_news)
        diverged = 0
        for i in range(len(prompts)):
            assert of[i][0] == oq[i][0], "first token must be exact"
            if not (of[i] == oq[i]).all():
                diverged += 1
        rate = diverged / len(prompts)
        assert rate <= 0.5, f"int8 divergence rate {rate} > 0.5 bound"
    finally:
        fp.shutdown()
        q8.shutdown()


def test_int8_spec_decode_identity(nano, nano_params):
    """Speculative decoding on a quantized pool: the verify forward
    reads the SAME int8 cache as plain decode, so spec on vs off is
    token-identical at temp 0 — acceptance arithmetic never sees the
    quantization, only the committed tokens do."""
    plain = _make(nano, nano_params, prefix_cache=False,
                  kv_dtype="int8")
    spec = _make(nano, nano_params, prefix_cache=False,
                 kv_dtype="int8", spec_decode="ngram", draft_k=4)
    try:
        rng = np.random.default_rng(7)
        base = rng.integers(0, nano.vocab_size, (4,)).astype(np.int32)
        prompts = [np.tile(base, 3)[:n] for n in (9, 12)]  # repetitive
        max_news = [10, 8]
        op = _drain_concurrent(plain, prompts, max_news)
        os_ = _drain_concurrent(spec, prompts, max_news)
        for i in range(2):
            assert (op[i] == os_[i]).all(), (i, op[i], os_[i])
    finally:
        plain.shutdown()
        spec.shutdown()


# ------------------------------------------------------- quantized handoff
def test_quantized_handoff_roundtrip(nano, nano_params):
    """int8 prefill engine -> int8 decode engine: the payload ships
    CODES + per-page scales, the digest covers both, and the decode
    stream is token-identical to an uninterrupted run on one int8
    engine. Tampering with a shipped scale fails byte-verification and
    degrades to the counted local re-prefill; so does landing the int8
    payload on an fp engine (layout mismatch)."""
    kw = dict(page_size=8, prefix_cache=False,
              kv_dtype="int8")
    pre = _make(nano, nano_params, role="prefill", **kw)
    dec = _make(nano, nano_params, role="decode", **kw)
    ref_eng = _make(nano, nano_params, **kw)
    fp_dec = _make(nano, nano_params, role="decode", page_size=8, prefix_cache=False, kv_dtype="fp")
    try:
        rng = np.random.default_rng(8)
        prompt = rng.integers(0, nano.vocab_size, (11,)).astype(np.int32)
        ref = np.concatenate(list(ref_eng.stream(prompt, 10, seed=3)))
        desc = pre.handoff(prompt, 10, seed=3)
        payload = desc["payload"]
        assert payload["k"].dtype == np.int8
        assert payload["kv_dtype"] == "int8"
        assert payload["page_size"] == 8
        assert payload["ks"].shape == (nano.n_layer, 2, nano.n_head)
        out = _drain(dec.admit_prefilled(desc))
        assert (out == ref).all(), (out, ref)
        assert dec.stats()["handoff"]["imported"] == 1
        # Scale tamper: the digest covers the scales, so a flipped
        # scale fails verification -> local re-prefill, same tokens.
        bad = dict(desc)
        bad["payload"] = dict(payload)
        bad["payload"]["ks"] = np.array(payload["ks"])
        bad["payload"]["ks"][0, 0, 0] *= 2
        out_t = _drain(dec.admit_prefilled(bad))
        assert (out_t == ref).all()
        assert dec.stats()["handoff"]["import_fallbacks"] == 1
        # Layout mismatch: int8 payload on an fp engine falls back to
        # a local fp re-prefill (token-identical by determinism).
        fp_ref = np.concatenate(list(
            _ref_fp_stream(nano, nano_params, prompt)))
        out_fp = _drain(fp_dec.admit_prefilled(desc))
        assert (out_fp == fp_ref).all()
        assert fp_dec.stats()["handoff"]["import_fallbacks"] == 1
        assert fp_dec.stats()["handoff"]["imported"] == 0
    finally:
        pre.shutdown()
        dec.shutdown()
        ref_eng.shutdown()
        fp_dec.shutdown()


def _ref_fp_stream(nano, nano_params, prompt):
    eng = _make(nano, nano_params, page_size=8,
                prefix_cache=False, kv_dtype="fp")
    try:
        return list(eng.stream(prompt, 10, seed=3))
    finally:
        eng.shutdown()


# ------------------------------------------------------- program budget
def test_recompile_guard_both_knobs(nano, nano_params):
    """With attn_kernel=pallas AND kv_dtype=int8 the compiled-program
    set is STILL a prefill program a prompt bucket and one a pair of
    buckets (the group of one chunk boundary; ``warm_up()`` runs them
    all) + 1 fused chunk program — quantization scatter, scale
    updates, and the
    kernel dispatch are all inside the same jitted programs, keyed by
    static knobs only. page_size=24 is unique to this test, so the
    (process-wide, lru-shared) wrappers count only this pool's
    programs."""
    from ray_tpu.models.gpt_decode import (jit_decode_chunk_slots_paged,
                                           jit_prefill_into_slot_paged)

    eng = _make(nano, nano_params, slots=3, max_len=48,
                prompt_buckets=(8, 16), page_size=24,
                prefix_cache=True, kv_dtype="int8",
                attn_kernel="pallas")
    try:
        rng = np.random.default_rng(9)
        sysp = rng.integers(0, nano.vocab_size, (12,)).astype(np.int32)

        def storm(lens):
            prompts = []
            for i, n in enumerate(lens):
                if i % 3 == 0:
                    tail = rng.integers(0, nano.vocab_size,
                                        (4,)).astype(np.int32)
                    prompts.append(np.concatenate([sysp, tail]))
                else:
                    prompts.append(rng.integers(
                        0, nano.vocab_size, (int(n),)).astype(np.int32))
            _drain_concurrent(eng, prompts,
                              [int(rng.integers(1, 10))
                               for _ in prompts])

        eng.warm_up()
        storm([5, 16, 8])                     # warm every bucket
        pre_prefill = eng._prefill._cache_size()
        pre_step = eng._step._cache_size()
        n = len(eng.prompt_buckets)
        assert pre_prefill == n + n * (n + 1) // 2
        assert pre_step == 1
        storm([1, 3, 7, 9, 12, 15, 16, 2])    # mixed-shape storm
        assert eng._prefill._cache_size() == pre_prefill
        assert eng._step._cache_size() == pre_step
        # lru wrappers keyed on the FULL static-knob tuple.
        assert jit_prefill_into_slot_paged(nano, 24, 0.0, "int8") \
            is eng._prefill
        assert jit_decode_chunk_slots_paged(
            nano, 4, 24, 0.0, -1, "int8", "pallas") is eng._step
    finally:
        eng.shutdown()


# ----------------------------------------------------------- plumbing
def test_knob_validation_and_plumbing(nano, nano_params):
    """Config-plane guards: the knobs are paged-pool-only and
    validated everywhere they enter — engine ctor, ensure_paging,
    @serve.batch, and the deployment schema."""
    from ray_tpu.serve import batching
    from ray_tpu.serve.schema import DeploymentSchema

    with pytest.raises(ValueError, match="attn_kernel"):
        _make(nano, nano_params, attn_kernel="fused")
    with pytest.raises(ValueError, match="kv_dtype"):
        _make(nano, nano_params, kv_dtype="int4")
    with pytest.raises(ValueError, match="continuous"):
        batching.batch(kv_dtype="int8")(lambda xs: xs)
    with pytest.raises(ValueError, match="continuous"):
        batching.batch(attn_kernel="pallas")(lambda xs: xs)
    DeploymentSchema.from_dict({
        "name": "d",
        "engine": {"page_size": 8, "kv_dtype": "int8",
                   "attn_kernel": "pallas"}})
    with pytest.raises(ValueError, match="unknown engine config"):
        DeploymentSchema.from_dict({"name": "d",
                                    "engine": {"kv_dtyp": "int8"}})
    # Live reconfigure through the same applier the deployment path
    # uses: a fresh engine + knobs repages; knob change rebuilds the
    # pool.
    eng = _make(nano, nano_params, page_size=16)
    try:
        fp_pages = eng.n_pages
        eng.apply_config(page_size=8, kv_dtype="int8",
                         attn_kernel="pallas")
        assert eng.page_size == 8 and eng.kv_dtype == "int8"
        assert eng.attn_kernel == "pallas"
        assert eng.n_pages > 2 * fp_pages       # half the page, ~half the bytes
        st = eng.stats()
        assert st["kv_dtype"] == "int8"
        assert st["kv_bytes_per_token"] < 2 * nano.n_layer * \
            nano.n_head * nano.head_dim * 2   # below the bf16 cost
        out = np.concatenate(list(eng.stream(
            np.arange(5, dtype=np.int32) % nano.vocab_size, 4)))
        assert out.shape == (4,)
    finally:
        eng.shutdown()


def test_kv_bytes_per_page_accounting(nano):
    """The sizing fix: ``kv_bytes_per_page`` charges the CONFIGURED
    element size (int8 codes + amortized f32 scales), so the default
    ``n_pages`` budget admits ~2x lanes — not the param dtype."""
    from ray_tpu.models import gpt_decode

    fp = gpt_decode.kv_bytes_per_page(nano, 8)
    i8 = gpt_decode.kv_bytes_per_page(nano, 8, "int8")
    assert fp == nano.n_layer * 2 * 8 * nano.n_head * nano.head_dim * 2
    assert i8 == nano.n_layer * 2 * (8 * nano.n_head * nano.head_dim
                                     + 4 * nano.n_head)
    assert fp / i8 > 1.5


# ------------------------------------------- the pool is carried (PR 25)
def _pool_case(nano, kv_dtype, n_pages, rng=None):
    """A 4-slot paged pool over nano with every addressing case of a
    decode or verify step in it. Slot 0 writes mid-page behind
    ``PT_SENTINEL`` columns; slot 1 is inactive; slot 2's write target
    is unmapped (``pos`` lies in a sentinel column); slot 3 holds a
    full, out-of-order table and starts a new page. With ``rng`` the
    pool is filled with noise, so a row landing in any page of any
    layer that the step should not touch shows."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode

    ps, max_pages = 8, 4
    cache = gpt_decode.init_paged_cache(nano, 4, n_pages, ps, kv_dtype)
    if rng is not None:
        for name, a in cache.items():
            if name in ("k", "v"):
                noise = rng.integers(-127, 128, a.shape) \
                    if kv_dtype == "int8" \
                    else rng.standard_normal(a.shape)
                cache[name] = jnp.asarray(noise, a.dtype)
            elif name in ("ks", "vs"):
                cache[name] = jnp.asarray(
                    rng.uniform(0.01, 0.05, a.shape), a.dtype)
    pt = np.full((4, max_pages), gpt_decode.PT_SENTINEL, np.int32)
    pt[0, :2] = [5, 3]
    pt[1, :1] = [6]
    pt[2, :2] = [9, 1]
    pt[3, :4] = [7, 0, 10, 2]
    cache["pos"] = jnp.asarray([12, 4, 16, 24], jnp.int32)
    active = jnp.asarray([True, False, True, True])
    token = jnp.asarray([3, 5, 7, 11], jnp.int32)
    return cache, token, active, jnp.asarray(pt), ps


def _eqns(jaxpr, primitive):
    """Every equation of a jaxpr that binds ``primitive``, nested ones
    included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for sub in eqn.params.values():
            for j in sub if isinstance(sub, (list, tuple)) else (sub,):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, primitive)


@pytest.mark.parametrize("program,kv_dtype,attn_kernel", [
    ("decode", "fp", "gather"), ("decode", "fp", "pallas"),
    ("decode", "int8", "gather"), ("decode", "int8", "pallas"),
    ("verify", "fp", "gather"), ("verify", "int8", "gather")])
def test_pool_is_carried_not_scanned(nano, nano_params, program,
                                     kv_dtype, attn_kernel):
    """No ``scan`` of the chunk and verify programs takes or returns
    the pool through ``xs``/``ys``: scanned, XLA slices every layer's
    pool out of the stacked pool and writes it back in every token
    step (a quarter to two fifths of a decode step on the chip, PR 23).
    The pool is larger here than any stacked weight, so one layer's
    worth of elements among the scanned operands can only be the pool;
    the int8 scales are small and told by their shapes."""
    import jax

    from ray_tpu.models import gpt_decode

    n_pages = 1024
    cache, token, active, pt, ps = _pool_case(nano, kv_dtype, n_pages)
    rngs = jax.random.split(jax.random.PRNGKey(0), 4)
    if program == "decode":
        jaxpr = jax.make_jaxpr(lambda c: gpt_decode.decode_chunk_slots_paged(
            nano_params, c, token, rngs, active, pt, cfg=nano, k=2,
            page_size=ps, kv_dtype=kv_dtype,
            attn_kernel=attn_kernel))(cache)
    else:
        draft = np.zeros((4, 2), np.int32)
        jaxpr = jax.make_jaxpr(lambda c: gpt_decode.verify_chunk_slots_paged(
            nano_params, c, token, draft, rngs, active, pt, cfg=nano,
            k=2, page_size=ps, kv_dtype=kv_dtype))(cache)
    layer_elems = cache["k"][0].size
    assert layer_elems > max(
        a.size for a in jax.tree_util.tree_leaves(nano_params))
    scale_shapes = {cache[n].shape[i:] for n in ("ks", "vs")
                    if n in cache for i in (0, 1)}
    carried = False
    for eqn in _eqns(jaxpr.jaxpr, "scan"):
        n_fixed = eqn.params["num_consts"] + eqn.params["num_carry"]
        scanned = list(eqn.invars[n_fixed:]) \
            + list(eqn.outvars[eqn.params["num_carry"]:])
        for var in scanned:
            assert var.aval.size < layer_elems, var.aval
            assert var.aval.shape not in scale_shapes, var.aval
        carried |= any(
            v.aval.size == cache["k"].size
            for v in eqn.outvars[:eqn.params["num_carry"]])
    assert carried


def _per_layer_reference(nano_params, cache, layer_fn, x):
    """The layer scan as it was before the pool was carried: layer
    ``l``'s pool is sliced out of the stacked pool (the scan's ``xs``),
    handed to ``layer_fn(x, p, kc, vc, ksc, vsc)`` with the slots' page
    table as it is, and the layers' pools are stacked again (``ys``).
    Returns the last ``x`` and the stacked pool."""
    import jax

    names = [n for n in ("k", "v", "ks", "vs") if n in cache]

    def body(x, layer):
        p, pool = layer
        x, pool = layer_fn(x, p, *pool, *([None] * (4 - len(pool))))
        return x, pool[:len(names)]

    x, pool = jax.lax.scan(
        body, x, (nano_params["block"], tuple(cache[n] for n in names)))
    return x, dict(zip(names, pool))


@pytest.mark.parametrize("kv_dtype,attn_kernel", [
    ("fp", "gather"), ("fp", "pallas"),
    ("int8", "gather"), ("int8", "pallas")])
def test_decode_step_matches_per_layer_reference(nano, nano_params,
                                                 kv_dtype, attn_kernel):
    """The step that carries the stacked pool and addresses layer
    ``l``'s pages at ``l * n_pages + page`` gives the same logits and
    the same pool, bit for bit, as the step that slices each layer's
    pool, scatters, attends and stacks — over sentinel columns, an
    inactive slot and an unmapped write target, on a pool of noise."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode as gd
    from ray_tpu.models.gpt import _project_vocab, _rmsnorm

    cache, token, active, pt, ps = _pool_case(
        nano, kv_dtype, 12, np.random.default_rng(31))
    pos = cache["pos"]
    page_idx = jnp.take_along_axis(
        pt, jnp.clip(pos // ps, 0, pt.shape[1] - 1)[:, None], axis=1)[:, 0]
    page_w = jnp.where(active & (pos // ps < pt.shape[1]), page_idx,
                       jnp.int32(gd.PT_SENTINEL))

    def layer_fn(x, p, kc, vc, ksc, vsc):
        q, k, v = gd._block_kv(x, p, nano)
        if kv_dtype == "int8":
            kc, ksc = gd._merge_span_int8(kc, ksc, k, pt, pos, 1,
                                          active, ps)
            vc, vsc = gd._merge_span_int8(vc, vsc, v, pt, pos, 1,
                                          active, ps)
        else:
            kc = kc.at[page_w, pos % ps].set(k[:, 0], mode="drop")
            vc = vc.at[page_w, pos % ps].set(v[:, 0], mode="drop")
        att = gd.paged_attention(q, kc, vc, pt, pos, page_size=ps,
                                 kernel=attn_kernel, ks=ksc, vs=vsc)
        x = x + gd._mm_row(att.reshape(4, 1, -1), p["wo"]["kernel"],
                           nano.dtype)
        return gd._ffn(x, p, nano), (kc, vc, ksc, vsc)

    @jax.jit
    def reference(cache):
        x = nano_params["embed"]["kernel"].astype(nano.dtype)[token]
        x = x + nano_params["pos_embed"][pos].astype(nano.dtype)
        x, pool = _per_layer_reference(nano_params, cache, layer_fn,
                                       x[:, None])
        x = _rmsnorm(x, nano_params["ln_f_scale"])
        logits = _project_vocab(x, nano_params["embed"]["kernel"], nano)
        return logits[:, 0], {**pool, "pos": pos + active}

    step = jax.jit(lambda c: gd._slot_decode_step_paged(
        nano_params, c, token, active, pt, nano, ps, kv_dtype,
        attn_kernel))
    want_logits, want = reference(cache)
    got_logits, got = step(cache)
    # Slot 2 attends at a position whose page is unmapped. The kernel
    # skips the column; the gather reads whichever page the sentinel
    # clips to, so that row's logits are noise here as they were before
    # (the engine parks a slot it cannot cover, never steps it).
    rows = [0, 1, 3] if attn_kernel == "gather" else [0, 1, 2, 3]
    assert np.array_equal(np.asarray(got_logits, np.float32)[rows],
                          np.asarray(want_logits, np.float32)[rows])
    assert sorted(got) == sorted(want)
    for name in want:
        assert got[name].shape == cache[name].shape
        assert np.array_equal(np.asarray(got[name], np.float32),
                              np.asarray(want[name], np.float32)), name
    # And the step did write: the pages of slots 0 and 3 at their
    # positions, in every layer, and no other page of any layer.
    changed = np.asarray(got["k"] != cache["k"]).any(axis=(2, 3, 4))
    assert changed.tolist() == [[p in (2, 3) for p in range(12)]] * 2


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_verify_matches_per_layer_reference(nano, nano_params, kv_dtype):
    """The speculative verify carries the same pool the decode step
    does (the spec engine alternates the two): k+1 rows a slot, the
    same per-layer reference, bit-equal logits-derived outputs and
    pool."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt_decode as gd
    from ray_tpu.models.gpt import _project_vocab, _rmsnorm

    k, S = 2, 3
    cache, token, active, pt, ps = _pool_case(
        nano, kv_dtype, 12, np.random.default_rng(32))
    pos = cache["pos"]
    draft = jnp.asarray([[1, 2], [3, 4], [5, 6], [7, 8]], jnp.int32)
    rngs = jax.random.split(jax.random.PRNGKey(1), 4)
    positions = pos[:, None] + jnp.arange(S)[None, :]
    vp = positions // ps
    page_w = jnp.where(
        active[:, None] & (vp < pt.shape[1]),
        jnp.take_along_axis(pt, jnp.clip(vp, 0, pt.shape[1] - 1), axis=1),
        jnp.int32(gd.PT_SENTINEL))
    V = pt.shape[1] * ps
    valid = jnp.arange(V)[None, None, None, :] \
        <= positions[:, None, :, None]
    ptc = jnp.clip(pt, 0, 11)

    def layer_fn(x, p, kc, vc, ksc, vsc):
        q, kk, vv = gd._block_kv(x, p, nano)
        if kv_dtype == "int8":
            kc, ksc = gd._merge_span_int8(kc, ksc, kk, pt, pos, S,
                                          active, ps)
            vc, vsc = gd._merge_span_int8(vc, vsc, vv, pt, pos, S,
                                          active, ps)
            hk = gd._deq_page(kc[ptc], ksc[ptc], q.dtype)
            hv = gd._deq_page(vc[ptc], vsc[ptc], q.dtype)
        else:
            kc = kc.at[page_w, positions % ps].set(kk, mode="drop")
            vc = vc.at[page_w, positions % ps].set(vv, mode="drop")
            hk, hv = kc[ptc], vc[ptc]
        hk = hk.reshape(4, V, -1, nano.head_dim)
        hv = hv.reshape(4, V, -1, nano.head_dim)
        lg = jnp.einsum("bqhd,bkhd->bhqk", q, hk,
                        preferred_element_type=jnp.float32) \
            / jnp.sqrt(jnp.asarray(nano.head_dim, jnp.float32))
        probs = jax.nn.softmax(jnp.where(valid, lg, -1e30),
                               axis=-1).astype(q.dtype)
        att = jnp.einsum("bhqk,bkhd->bqhd", probs, hv,
                         preferred_element_type=jnp.float32
                         ).astype(q.dtype).reshape(4, S, -1)
        x = x + gd._mm_row(att, p["wo"]["kernel"], nano.dtype)
        return gd._ffn(x, p, nano), (kc, vc, ksc, vsc)

    @jax.jit
    def reference(cache):
        seq = jnp.concatenate([token[:, None], draft], axis=1)
        x = nano_params["embed"]["kernel"].astype(nano.dtype)[seq]
        x = x + nano_params["pos_embed"][positions].astype(nano.dtype)
        x, pool = _per_layer_reference(nano_params, cache, layer_fn, x)
        x = _rmsnorm(x, nano_params["ln_f_scale"])
        logits = _project_vocab(x, nano_params["embed"]["kernel"], nano)
        committed, n_acc, _ = gd._spec_accept(logits, draft, rngs, 0.0, k)
        return committed, n_acc, pool

    verify = jax.jit(lambda c: gd.verify_chunk_slots_paged(
        nano_params, c, token, draft, rngs, active, pt, cfg=nano, k=k,
        page_size=ps, kv_dtype=kv_dtype))
    want_committed, want_acc, want = reference(cache)
    committed, n_acc, got, _ = verify(cache)
    rows = [0, 1, 3]     # slot 2 attends an unmapped page: noise, as above
    assert np.array_equal(np.asarray(committed)[rows],
                          np.asarray(want_committed)[rows])
    assert np.array_equal(np.asarray(n_acc)[rows],
                          np.asarray(want_acc)[rows])
    for name in want:
        assert got[name].shape == cache[name].shape
        assert np.array_equal(np.asarray(got[name], np.float32),
                              np.asarray(want[name], np.float32)), name
    changed = np.asarray(got["k"] != cache["k"]).any(axis=(2, 3, 4))
    assert changed.tolist() == [[p in (2, 3) for p in range(12)]] * 2
