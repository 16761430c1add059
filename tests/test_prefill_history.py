"""A paged prefill's attention reads the history it was given, not
``max_len`` of it (``serving.attend_history``): the suffix attends
over itself, and the cached prefix is read through the page table a
block at a time under a loop of ``ceil(hist_len / block)`` trips.

The oracle is the view the programs had before: the slot's WHOLE page
table gathered, ``max_len`` keys beside the suffix's, masked below
``hist_len``, one softmax over all of them. It is kept here as plain
functions (:func:`_oracle_gpt`, :func:`_oracle_latent_attention`) and
run in the programs' own frames. The three descriptions with a history
view are judged alike: ``gpt_decode`` (fp and int8 pages), ``mla_moe``
and ``scmoe`` (which imports ``mla_moe``'s frame for its two attentions
a layer), at float32 (where only the order of the sums differs) and at
the models' own bfloat16."""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import (gpt, gpt_decode as gd, mla_moe, scmoe,
                            serving)
from ray_tpu.models.serving import PT_SENTINEL

PS = 8                     # tokens a page
MAX_LEN = 1024             # the slot's reach: 128 pages
N_PAGES = 3 * MAX_LEN // PS
BUCKET = 32                # the suffix's bucket
SUFFIX = 27                # its live rows
BLOCK = serving.HIST_BLOCK_TOKENS
#: a hit of one partial block, of exactly one block, of several blocks
#: (and a partial last one), and one that ends mid-page: its last page
#: is forked, not shared
HITS = {"partial_block": 104, "one_block": BLOCK,
        "several_blocks": 2 * BLOCK + 88, "mid_page_cow": BLOCK + 45}
#: |delta| of the last row's logits over the largest logit. Every
#: probability is the one softmax's own in both views, so they differ
#: by the order of float32 sums: ``tests/test_serve_engine_scmoe.py``'s
#: TOL at float32, and at bfloat16 two ulps of the largest logit (most
#: hits read 0: the sums round to the same bfloat16; 0.0038 the largest
#: seen, on int8 pages where a scale moved)
TOLS = {"float32": 2e-5, "bfloat16": 2 ** -7}
#: a hit on int8 pages against the whole prompt at once reads the
#: prefix through the quantiser, the whole prefill does not
INT8_WHOLE_TOL = 0.05

DESCRIPTIONS = ("gpt-fp", "gpt-int8", "mla_moe", "scmoe")


# ---------------------------------------------------------------- oracle
def _oracle_gpt(params, cache, tokens, length, hist_len, pt_row, cow_src,
                slot, rng, *, cfg, page_size, temperature=0.0,
                kv_dtype="fp"):
    """``gpt_decode.prefill_into_slot_paged`` as it was: ``hk``, ``hv``
    ``[L, V, H, hd]`` gathered for every layer before the scan, scores
    ``[H, S, V + S]`` a layer."""
    B, S = tokens.shape
    L, hd = cfg.n_layer, cfg.head_dim
    n_pages = cache["k"].shape[1]
    ps = page_size
    max_pages = pt_row.shape[0]
    V = max_pages * ps
    scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    positions = hist_len + jnp.arange(S)
    x = params["embed"]["kernel"].astype(cfg.dtype)[tokens]
    x = x + jnp.take(params["pos_embed"],
                     jnp.clip(positions, 0,
                              params["pos_embed"].shape[0] - 1),
                     axis=0).astype(cfg.dtype)[None]
    dst = pt_row[jnp.clip(hist_len // ps, 0, max_pages - 1)]
    dst_w = jnp.where(cow_src < n_pages, dst, jnp.int32(PT_SENTINEL))
    src_c = jnp.clip(cow_src, 0, n_pages - 1)
    pool = {n: cache[n].at[:, dst_w].set(cache[n][:, src_c], mode="drop")
            for n in cache if n != "pos"}
    quant = kv_dtype == "int8"
    ptc = jnp.clip(pt_row, 0, n_pages - 1)
    if quant:
        hk = gd._deq_page(pool["k"][:, ptc], pool["ks"][:, ptc],
                          cfg.dtype).reshape(L, V, -1, hd)
        hv = gd._deq_page(pool["v"][:, ptc], pool["vs"][:, ptc],
                          cfg.dtype).reshape(L, V, -1, hd)
    else:
        hk = pool["k"][:, ptc].reshape(L, V, -1, hd)
        hv = pool["v"][:, ptc].reshape(L, V, -1, hd)
    hist_valid = (jnp.arange(V) < hist_len)[None, None, None, :]
    self_mask = jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None]

    def body(x, layer):
        p, hk_l, hv_l = layer
        q, k, v = gd._block_kv(x, p, cfg)
        lg_h = jnp.einsum("bqhd,khd->bhqk", q, hk_l,
                          preferred_element_type=jnp.float32) * scale
        lg_h = jnp.where(hist_valid, lg_h, -1e30)
        lg_s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                          preferred_element_type=jnp.float32) * scale
        lg_s = jnp.where(self_mask, lg_s, -1e30)
        probs = jax.nn.softmax(jnp.concatenate([lg_h, lg_s], axis=-1),
                               axis=-1).astype(q.dtype)
        vv = jnp.concatenate([hv_l[None].astype(q.dtype), v], axis=1)
        att = jnp.einsum("bhqk,bkhd->bqhd", probs, vv,
                         preferred_element_type=jnp.float32
                         ).astype(q.dtype).reshape(B, S, -1)
        x = x + gd._mm_row(att, p["wo"]["kernel"], cfg.dtype, None)
        return gd._ffn(x, p, cfg, None), (k[0], v[0])

    x, (k_new, v_new) = lax.scan(body, x, (params["block"], hk, hv))
    x = gd._rmsnorm(x, params["ln_f_scale"])
    x_last = lax.dynamic_slice(x, (0, length - 1, 0), (1, 1, cfg.d_model))
    logits = gd._project_vocab(x_last, params["embed"]["kernel"], cfg)
    token, rng = serving.sample(logits[:, 0], temperature, rng)
    pos = lax.dynamic_update_slice(
        cache["pos"], jnp.reshape(hist_len + length, (1,)), (slot,))
    if quant:
        one = jnp.ones((1,), jnp.bool_)
        merge = jax.vmap(lambda c, s, vl: gd._merge_span_int8(
            c, s, vl[None], pt_row[None], jnp.reshape(hist_len, (1,)),
            length, one, ps))
        kc, ksc = merge(pool["k"], pool["ks"], k_new)
        vc, vsc = merge(pool["v"], pool["vs"], v_new)
        return token[0], {"k": kc, "v": vc, "ks": ksc, "vs": vsc,
                          "pos": pos}, rng
    vp = positions // ps
    ok = (jnp.arange(S) < length) & (vp < max_pages)
    page_w = jnp.where(ok, pt_row[jnp.clip(vp, 0, max_pages - 1)],
                       jnp.int32(PT_SENTINEL))
    return token[0], {
        "k": pool["k"].at[:, page_w, positions % ps].set(k_new,
                                                         mode="drop"),
        "v": pool["v"].at[:, page_w, positions % ps].set(v_new,
                                                         mode="drop"),
        "pos": pos}, rng


def _oracle_latent_attention(cache, S, length, hist_len, pt_row, cow_src,
                             cfg, page_size):
    """``mla_moe.prefill_attention`` as it was: every attention gathers
    the slot's whole page table, ``V = max_len`` latent rows, and
    materialises keys and values for all ``V + S`` of them."""
    ps = page_size
    A, n_pages = cache["latent"].shape[:2]
    max_pages = pt_row.shape[0]
    V = max_pages * ps
    positions = hist_len + jnp.arange(S)
    pool = serving.flat(cache["latent"])
    layers = jnp.arange(A, dtype=jnp.int32) * n_pages
    dst = pt_row[jnp.clip(hist_len // ps, 0, max_pages - 1)]
    dst_w = jnp.where((cow_src < n_pages) & (dst < n_pages),
                      dst + layers, jnp.int32(PT_SENTINEL))
    pool = pool.at[dst_w].set(
        pool[jnp.clip(cow_src, 0, n_pages - 1) + layers], mode="drop")
    ptc = jnp.clip(pt_row, 0, n_pages - 1)
    seen = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(V) < hist_len, (S, V)),
        jnp.tril(jnp.ones((S, S), jnp.bool_))], axis=1)[None, None]
    live = jnp.arange(S) < length
    vp = positions // ps
    page_w = jnp.where(live & (vp < max_pages),
                       pt_row[jnp.clip(vp, 0, max_pages - 1)],
                       jnp.int32(PT_SENTINEL))

    def attend(x, p, a, pool):
        qn, qr, ent = mla_moe.latent_projections(
            x, p, positions[None], cfg)[2:]
        latents = jnp.concatenate(
            [pool[ptc + a * n_pages].reshape(1, V, -1), ent], axis=1)
        w_uk, w_uv = mla_moe.wkvb(p, cfg)
        c = latents[..., :cfg.kv_rank]
        kr = latents[..., cfg.kv_rank:cfg.latent_dim]
        kn = jnp.einsum("bkr,rhn->bkhn", c, w_uk,
                        preferred_element_type=jnp.float32
                        ).astype(cfg.dtype)
        v = jnp.einsum("bkr,rhv->bkhv", c, w_uv,
                       preferred_element_type=jnp.float32
                       ).astype(cfg.dtype)
        lg = jnp.einsum("bqhn,bkhn->bhqk", qn, kn,
                        preferred_element_type=jnp.float32) \
            + jnp.einsum("bqhr,bkr->bhqk", qr, kr,
                         preferred_element_type=jnp.float32)
        lg = jnp.where(seen, lg * cfg.attn_scale, -1e30)
        probs = jax.nn.softmax(lg, axis=-1).astype(cfg.dtype)
        att = jnp.einsum("bhqk,bkhv->bqhv", probs, v,
                         preferred_element_type=jnp.float32
                         ).astype(cfg.dtype).reshape(1, S, -1)
        x = x + gpt._mm(att, p["wo"]["kernel"], cfg.dtype
                        ).astype(x.dtype)
        return x, pool.at[serving.at_layer(page_w, a, n_pages),
                          positions % ps].set(ent[0], mode="drop")

    return pool, live, attend


# --------------------------------------------------------------- harness
def _logits_for_token(logits, temperature, key):
    """In ``_sample``'s place: the program hands back the last row's
    LOGITS where it would hand back the token chosen from them."""
    return logits, key


@dataclasses.dataclass
class Case:
    """One description at one dtype: the program as it stands and the
    oracle, each jitted ONCE a bucket (``hist_len`` is traced), both
    returning the last row's logits in the token's place."""
    name: str
    cfg: object
    params: object
    kv_dtype: str
    new: object
    old: object
    init: object

    def run(self, which, cache, prompt, hist_len, pt_row,
            cow_src=PT_SENTINEL, slot=0):
        """Prefill ``prompt[hist_len:]`` behind ``hist_len`` cached
        tokens: ``(logits [rows] float32, cache')``."""
        suffix = np.asarray(prompt[hist_len:], np.int32)
        bucket = BUCKET if len(suffix) <= BUCKET else MAX_LEN
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(suffix)] = suffix
        logits, cache, _ = getattr(self, which)(
            self.params, cache, jnp.asarray(tokens),
            jnp.asarray(len(suffix), jnp.int32),
            jnp.asarray(hist_len, jnp.int32),
            jnp.asarray(pt_row, jnp.int32),
            jnp.asarray(cow_src, jnp.int32), jnp.asarray(slot, jnp.int32),
            jax.random.PRNGKey(0))
        return np.asarray(logits, np.float32), cache


@functools.lru_cache(maxsize=None)
def _build(name, dtype):
    dt = jnp.dtype(dtype)
    key = jax.random.PRNGKey(3)
    if name.startswith("gpt"):
        kv_dtype = name.split("-")[1]
        cfg = dataclasses.replace(gpt.CONFIGS["nano"], max_seq=MAX_LEN,
                                  dtype=dt, param_dtype=dt)
        params = gpt.init_params(key, cfg)
        new, old = gd.prefill_into_slot_paged, _oracle_gpt
        init = lambda: gd.init_paged_cache(cfg, 2, N_PAGES, PS, kv_dtype)
    else:
        desc = {"mla_moe": mla_moe, "scmoe": scmoe}[name]
        kv_dtype = "fp"
        cfg = dataclasses.replace(desc.CONFIGS["nano"], dtype=dt,
                                  param_dtype=dt)
        params = desc.init_params(key, cfg)
        new = desc.prefill_into_slot_paged

        def old(*args, **kw):
            # the module's own frame around the oracle's attention
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(desc, "prefill_attention",
                           _oracle_latent_attention)
                return new(*args, **kw)

        init = lambda: desc.init_paged_cache(cfg, 2, N_PAGES, PS)
    knobs = dict(cfg=cfg, page_size=PS, kv_dtype=kv_dtype)
    return Case(name, cfg, params, kv_dtype,
                jax.jit(serving.program(new, "new", **knobs)),
                jax.jit(serving.program(old, "old", **knobs)), init)


@pytest.fixture(scope="module")
def logits_out():
    """While this module's programs are traced, the frame's ``sample``
    hands back the logits (every description reads it off ``serving``)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(serving, "sample", _logits_for_token)
        yield


@pytest.fixture(params=[(n, d) for n in DESCRIPTIONS for d in TOLS],
                ids="-".join)
def case(request, logits_out):
    return _build(*request.param)


def _prompt(cfg, n, seed=5):
    return np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n).astype(np.int32)


def _pages(first, n):
    """A table row of ``n`` pages from page ``first``, sentinels after."""
    row = np.full((MAX_LEN // PS,), PT_SENTINEL, np.int32)
    row[:n] = first + np.arange(n)
    return row


def _same_bits(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x.view(np.uint8), y.view(np.uint8))


def _pool_values(case, *caches):
    """``(name, values of each cache)`` in float32: int8 pages as the
    attention reads them, through their scales (a scale a rounding
    apart moves codes, not values)."""
    for name in caches[0]:
        if case.kv_dtype == "int8" and name in ("k", "v"):
            yield (name,) + tuple(np.asarray(gd._deq_page(
                c[name], c[name + "s"], jnp.float32)) for c in caches)
        else:
            yield (name,) + tuple(
                np.asarray(c[name]).astype(np.float32) for c in caches)


def _dist(a, b):
    return float(np.abs(a - b).max() / np.abs(b).max())


# ----------------------------------------------------------------- tests
def test_no_hit_is_the_old_view_to_the_bit(case):
    """``hist_len == 0``: the loop makes no trip, and the first token,
    the last row's logits and the written pages are the oracle's to the
    bit, at the small bucket and at the largest."""
    for n in (SUFFIX, MAX_LEN - 37):
        prompt = _prompt(case.cfg, n)
        row = _pages(5, -(-n // PS))
        got, cache = case.run("new", case.init(), prompt, 0, row, slot=1)
        want, cache_w = case.run("old", case.init(), prompt, 0, row,
                                 slot=1)
        assert got.argmax() == want.argmax()
        _same_bits(got, want)
        _same_bits(cache, cache_w)
        assert int(cache["pos"][1]) == n


@pytest.mark.parametrize("hit", list(HITS))
def test_a_hit_reads_its_blocks_and_answers_as_the_whole_view(case, hit):
    """A suffix behind a cached prefix, against the oracle on the same
    pages and against the whole prompt prefilled at once."""
    h = HITS[hit]
    tol = TOLS[case.cfg.dtype.name]
    prompt = _prompt(case.cfg, h + SUFFIX)
    n_pages = -(-len(prompt) // PS)
    # the first sender of the prefix: whole pages of it stay cached
    owner = _pages(7, -(-h // PS))
    _, cache = case.run("new", case.init(), prompt[:h], 0, owner, slot=1)
    row, cow = _pages(7, n_pages), PT_SENTINEL
    if h % PS:
        # the prefix ends mid-page: the hit maps the whole pages and
        # FORKS the last into a page of its own
        row[h // PS:n_pages] = 200 + np.arange(n_pages - h // PS)
        cow = owner[h // PS]
    else:
        assert hit != "mid_page_cow"
    got, cache_g = case.run("new", cache, prompt, h, row, cow)
    want, cache_w = case.run("old", cache, prompt, h, row, cow)
    assert _dist(got, want) <= tol, hit
    assert want.max() - want[got.argmax()] <= 2 * tol * np.abs(want).max()
    assert int(cache_g["pos"][0]) == len(prompt)
    # an int8 page holds what was written to a quantiser's step
    step = 1 / 127 if case.kv_dtype == "int8" else 0.0
    for name, g, w in _pool_values(case, cache_g, cache_w):
        assert np.abs(g - w).max() <= max(tol, step) * max(
            1.0, np.abs(w).max()), name
    if cow != PT_SENTINEL:
        # the owner's page is read, never written
        for name in cache:
            if name != "pos":
                _same_bits(cache[name][:, cow], cache_g[name][:, cow])
    whole, _ = case.run("new", case.init(), prompt, 0,
                        _pages(300, n_pages))
    assert _dist(got, whole) <= (
        INT8_WHOLE_TOL if case.kv_dtype == "int8" else tol), hit


@pytest.mark.parametrize("name", DESCRIPTIONS)
def test_the_program_does_not_grow_with_max_len(name, logits_out):
    """The bucket-512 prefill program costs the same operations at
    ``max_len`` 2048 and at 8192 (the old view's tripled): nothing in
    it is as wide as the slot's reach but the page table's row."""
    case = _build(name, "bfloat16")
    flops = []
    for max_len in (2048, 8192):
        cache = jax.eval_shape(case.init)
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        compiled = case.new.lower(
            case.params, cache,
            jax.ShapeDtypeStruct((1, 512), jnp.int32), i32, i32,
            jax.ShapeDtypeStruct((max_len // PS,), jnp.int32), i32, i32,
            jax.random.PRNGKey(0)).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        flops.append(cost["flops"])
    assert flops[0] > 0
    assert abs(flops[1] / flops[0] - 1) < 0.02, flops
