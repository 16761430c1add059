"""The prompts of one chunk boundary share one prefill launch
(``prefill_group_into_slots_paged`` of every description, the frame's
contract in ``models/serving.py``): a group of two, of mixed lengths,
one of them with a prefix hit that ends mid-page (a copy-on-write fork)
where the description has pages to share, leaves the pool, the per-slot
states, the positions, the first tokens and the keys that two single
prefills leave.

The oracle is the single program itself, run twice, each prompt in its
own bucket; the group takes each in its own bucket too, widest first
(as the engine hands a pair over), their rows end to end
(``serving.PromptRows``). The rows' own arithmetic is the same (a row
of a matrix product does not depend on the rows beside it; an expert's
wider blocks change no row), so the two agree to the order of float32
sums, and at bfloat16 to an ulp where such a sum rounds the other
way."""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import (gpt, gpt_decode as gd, kda_moe, mla_moe, scmoe,
                            serving, ssm_hybrid)
from ray_tpu.models.serving import PT_SENTINEL

PS = 4
SLOTS = 4
MAX_PAGES = 24
N_PAGES = SLOTS * MAX_PAGES
BUCKETS = (8, 16, 32)
#: |a - b| over max|b| of every array of the pool
TOLS = {"float32": 2e-5, "bfloat16": 2 ** -6}

DESCRIPTIONS = ("gpt-fp", "gpt-int8", "mla_moe", "scmoe", "kda_moe",
                "ssm_hybrid")
MODULES = {"gpt": gd, "mla_moe": mla_moe, "scmoe": scmoe,
           "kda_moe": kda_moe, "ssm_hybrid": ssm_hybrid}


@functools.lru_cache(maxsize=None)
def _build(name, dtype, temperature=0.0):
    dt = jnp.dtype(dtype)
    key = jax.random.PRNGKey(5)
    base, _, kv_dtype = name.partition("-")
    desc = MODULES[base]
    if base == "gpt":
        cfg = dataclasses.replace(gpt.CONFIGS["nano"], dtype=dt,
                                  param_dtype=dt)
        params = gpt.init_params(key, cfg)
    else:
        cfg = dataclasses.replace(desc.CONFIGS["nano"], dtype=dt,
                                  param_dtype=dt)
        params = desc.init_params(key, cfg)
    kv_dtype = kv_dtype or "fp"
    prog = desc.jit_prefill_into_slot_paged(cfg, PS, temperature, kv_dtype)
    return desc, cfg, params, kv_dtype, prog


def _prompts(cfg, lens, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _row(pages):
    row = np.full((MAX_PAGES,), PT_SENTINEL, np.int32)
    row[:len(pages)] = pages
    return row


def _bucket(n):
    return next(b for b in BUCKETS if b >= n)


def _padded(suffix):
    out = np.zeros((1, _bucket(len(suffix))), np.int32)
    out[0, :len(suffix)] = suffix
    return out


def _single(prog, params, cache, suffix, hist, pages, cow, slot, seed):
    return prog(params, cache, _padded(suffix), np.int32(len(suffix)),
                np.int32(hist), _row(pages), np.int32(cow), np.int32(slot),
                jax.random.PRNGKey(seed))


def _group(prog, params, cache, pair):
    """The pair in ONE launch, widest bucket first."""
    pair = sorted(pair, key=lambda a: -_bucket(len(a[0])))
    suffixes, hists, pages, cows, slots, seeds = zip(*pair)
    return prog(
        params, cache, tuple(_padded(s) for s in suffixes),
        np.asarray([len(s) for s in suffixes], np.int32),
        np.asarray(hists, np.int32), np.stack([_row(p) for p in pages]),
        np.asarray(cows, np.int32), np.asarray(slots, np.int32),
        jnp.stack([jax.random.PRNGKey(s) for s in seeds]))


def _admissions(name, cfg):
    """``(base, [(suffix, hist, pages, cow_src, slot, seed)] * 2)``: the
    two prompts of the boundary as the engine would hand them over, and
    the prompt whose pages the second one's hit maps (``None`` for a
    description without a prefix cache)."""
    long, short, base = _prompts(cfg, (21, 7, 13), seed=11)
    first = (long, 0, list(range(10, 16)), PT_SENTINEL, 1, 7)
    if "prefix_cache" in MODULES[name.partition("-")[0]].UNSUPPORTED:
        return None, [first, (short, 0, [20, 21], PT_SENTINEL, 3, 9)]
    # the base's 13 tokens sit in pages 0..3, the last one partial: the
    # hit maps the three whole pages and forks the fourth into page 30
    return base, [first, (short, 13, [0, 1, 2, 30, 31], 3, 3, 9)]


def _close(name, a, b, tol):
    a, b = (np.asarray(x.astype(jnp.float32)
                       if jnp.issubdtype(x.dtype, jnp.floating) else x)
            for x in (a, b))
    if a.dtype.kind in "iu":
        # int8 codes may round the other way where a sum did
        assert np.abs(a.astype(np.int64) - b.astype(np.int64)).max() <= (
            1 if a.dtype == np.int8 else 0), name
        return
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-6), name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", DESCRIPTIONS)
def test_a_group_of_two_leaves_what_two_single_prefills_leave(name, dtype):
    desc, cfg, params, kv_dtype, prog = _build(name, dtype)
    base, pair = _admissions(name, cfg)

    def start():
        cache = desc.init_paged_cache(cfg, SLOTS, N_PAGES, PS, kv_dtype)
        if base is not None:
            _, cache, _ = _single(prog, params, cache, base, 0,
                                  [0, 1, 2, 3], PT_SENTINEL, 0, 3)
        return cache

    cache = start()
    want = []
    for suffix, hist, pages, cow, slot, seed in pair:
        tok, cache, key = _single(prog, params, cache, suffix, hist, pages,
                                  cow, slot, seed)
        want.append((int(tok), np.asarray(key)))
    singles = cache

    suffixes, hists, pages = list(zip(*pair))[:3]
    toks, grouped, keys = _group(prog, params, start(), pair)

    assert [int(t) for t in toks] == [t for t, _ in want]
    for g, (_, key) in enumerate(want):
        assert (np.asarray(keys[g]) == key).all()
    assert set(grouped) == set(singles)
    assert list(np.asarray(grouped["pos"])) == list(np.asarray(singles["pos"]))
    assert int(grouped["pos"][3]) == hists[1] + len(suffixes[1])
    for entry in singles:
        assert grouped[entry].shape == singles[entry].shape
        assert grouped[entry].dtype == singles[entry].dtype
        _close(entry, grouped[entry], singles[entry], TOLS[dtype])
    # the group wrote something: the second prompt's last page holds rows
    written = next(e for e in ("k", "latent") if e in grouped)
    assert np.abs(np.asarray(grouped[written].astype(jnp.float32))
                  [:, pages[1][-1]]).max() > 0


@pytest.mark.parametrize("name", DESCRIPTIONS)
def test_a_group_samples_each_prompt_with_its_own_key(name):
    """Temperature above zero: each prompt's first token and the key it
    leaves in its lane are the single prefill's with that request's
    seed, whoever shares the launch."""
    desc, cfg, params, kv_dtype, prog = _build(name, "float32", 0.8)
    _, pair = _admissions(name, cfg)
    pair = [(s, 0, p, PT_SENTINEL, slot, seed)
            for s, _, p, _, slot, seed in pair]
    want = []
    for suffix, hist, pages, cow, slot, seed in pair:
        cache = desc.init_paged_cache(cfg, SLOTS, N_PAGES, PS, kv_dtype)
        tok, _, key = _single(prog, params, cache, suffix, hist, pages, cow,
                              slot, seed)
        want.append((int(tok), np.asarray(key)))
    seeds = [seed for *_, seed in pair]
    toks, _, keys = _group(
        prog, params,
        desc.init_paged_cache(cfg, SLOTS, N_PAGES, PS, kv_dtype), pair)
    assert [int(t) for t in toks] == [t for t, _ in want]
    for g, (_, key) in enumerate(want):
        assert (np.asarray(keys[g]) == key).all()
        assert not (key == np.asarray(jax.random.PRNGKey(seeds[g]))).all()
