"""The GPT block's decode attention ALONE, on the chip: the kernel of
``gpt_decode.paged_attention(kernel="pallas")`` at the serving cells'
pool (24 layers x 32,768 tokens of pages, 16 heads of 128, bfloat16 or
int8 codes), 24 layers x 8 steps in one program on the host's clock, as
PERF.md section 5 reads it since PR 35: 7 lanes x 150-300 live tokens,
32 x 250-450 (batch-offline's step), 16 x 1,500-2,000, each against
what HBM needs for the live pages at 819 GB/s, and within how many
bfloat16 ulps of the gather one layer lies.

    chiprun -- python benchmarks/gpt_attention_kernel.py [--int8]
        [--page-size 2048] [--parent DIR] [--sweep]

``--page-size`` takes the same tokens in larger pages (2,048: a page
a lane, read in parts); ``--parent DIR`` times the ``gpt_decode`` of
another checkout beside the tree's, in the same process on the same
pool; ``--sweep`` walks the blocks and rings of the grouped-query
kernel (PERF.md section 6, PR 61). ``--small`` is a CPU rehearsal of
the script, not a measurement. Writes ``chiprun_out/
gpt_attention_kernel[_int8][_ps<N>].json``.
"""
import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import gpt_decode as gd, kda_moe

H, HD, LAYER_TOKENS, MAX_LEN = 16, 128, 32768, 2048
#: name -> (live lanes of 32, fewest and most live tokens a lane)
CASES = {"7 lanes x 150-300": (7, 150, 300),
         "32 lanes x 250-450": (32, 250, 450),
         "16 lanes x 1500-2000": (16, 1500, 2000)}
#: (tokens, rows, ring) of a block under --sweep, the kept one first
SWEEP = [(128, 2048, 4), (128, 2048, 2), (128, 2048, 8), (64, 1024, 4),
         (64, 1024, 8), (256, 4096, 2), (256, 4096, 4)]
HBM_BYTES_PER_S = 819e9


def other_tree(path):
    """``gpt_decode`` of the checkout at ``path`` under a name of its
    own (its kernel, where it has one of its own, with it)."""
    spec = importlib.util.spec_from_file_location(
        "ray_tpu.models.gpt_decode_other",
        os.path.join(path, "ray_tpu", "models", "gpt_decode.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def program(attend, layers, n_pages, steps):
    """``steps`` decode steps of ``layers`` attentions each over the
    stacked pool, the next query made of the last context."""
    def run(q, kc, vc, pt, pos, *scales):
        def step(q, _):
            def layer(q, l):
                pt_l = jnp.where(pt == gd.PT_SENTINEL, pt, pt + l * n_pages)
                att = attend(q, kc, vc, pt_l, pos, *scales)
                return (q + 1e-3 * att).astype(q.dtype), None
            q, _ = lax.scan(layer, q, jnp.arange(layers))
            return q, None
        q, _ = lax.scan(step, q, None, length=steps)
        return q
    return jax.jit(run)


def case(rng, lanes, lo, hi, n_pages, ps, shrink, B=32):
    """B table rows of which ``lanes`` are live (the others all
    sentinel, pos 0), lengths uniform in [lo, hi], pages a seeded
    permutation; and the tokens HBM has to give."""
    pt = np.full((B, MAX_LEN // ps), gd.PT_SENTINEL, np.int32)
    pos = np.zeros((B,), np.int32)
    perm, off = rng.permutation(n_pages), 0
    live = rng.permutation(B)[:lanes]
    for b in live:
        n = max(1, int(rng.integers(lo, hi + 1)) // shrink)
        pages = -(-n // ps)
        pt[b, :pages] = perm[off:off + pages]
        off += pages
        pos[b] = n - 1
    # what HBM has to give: the live tokens in whole pages of 16, as
    # PR 35 counted them, whatever the pool's pages are
    return jnp.asarray(pt), jnp.asarray(pos), int(
        sum(-(-(p + 1) // 16) * 16 for p in pos[live]))


def timed(fn, *args, n=5):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(n):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return float(np.median(times)), float(min(times))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--int8", action="store_true")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--parent")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--small", action="store_true")
    a = ap.parse_args()
    ps = a.page_size
    layers, steps, shrink = (2, 2, 8) if a.small else (24, 8, 1)
    # a layer's pages: the cells' 32,768 tokens, or what the widest case
    # maps where pages are large, at fewer layers (the same bytes)
    n_pages = max(LAYER_TOKENS // shrink // ps,
                  max(n * -(-hi // shrink // ps)
                      for n, _, hi in CASES.values()))
    layers = max(1, layers * LAYER_TOKENS // shrink // (n_pages * ps))
    print(f"pool: {layers} layers x {n_pages} pages of {ps}", flush=True)
    print("device", jax.devices()[0].device_kind, flush=True)
    rng = np.random.default_rng(61)
    shape = (layers * n_pages, ps, H, HD)
    if a.int8:
        mk = jax.jit(lambda k: jax.random.randint(k, shape, -127, 128,
                                                  jnp.int8))
        sc = jax.jit(lambda k: jax.random.uniform(
            k, shape[:1] + (H,), jnp.float32, .005, .03))
    else:
        mk = jax.jit(lambda k: jax.random.normal(k, shape, jnp.bfloat16))
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(61), 5)
    kc, vc = mk(k1), mk(k2)
    scales = (sc(k3), sc(k4)) if a.int8 else ()
    q = jax.random.normal(k5, (32, 1, H, HD), jnp.bfloat16)

    def attention(mod, kernel, block=None):
        def attend(q, kc, vc, pt, pos, *s):
            if block:           # read when the program is traced
                (kda_moe._GQA_BLOCK_TOKENS, kda_moe._GQA_BLOCK_ROWS,
                 kda_moe._GQA_RING_BLOCKS) = block
            return mod.paged_attention(
                q, kc, vc, pt, pos, page_size=ps, kernel=kernel,
                ks=s[0] if s else None, vs=s[1] if s else None)
        return attend

    variants = {}
    if a.parent:
        variants["parent"] = attention(other_tree(a.parent), "pallas")
    for block in SWEEP if a.sweep else SWEEP[:1]:
        variants["tree T{} rows{} ring{}".format(*block)] = attention(
            gd, "pallas", block)
    gather = jax.jit(attention(gd, "gather"))

    out = {}
    for cname, (lanes, lo, hi) in CASES.items():
        pt, pos, tokens = case(rng, lanes, lo, hi, n_pages, ps, shrink)
        bound_ms = tokens * H * HD * kc.dtype.itemsize * 2 * layers \
            / HBM_BYTES_PER_S * 1e3
        alive = np.asarray(pt)[:, 0] != gd.PT_SENTINEL
        ref = np.asarray(gather(q, kc, vc, pt, pos, *scales),
                         np.float32)[alive]
        for vname, attend in variants.items():
            if lanes != 32 and vname not in (
                    "parent", "tree T128 rows2048 ring4"):
                continue
            try:
                got = np.asarray(jax.jit(attend)(q, kc, vc, pt, pos,
                                                 *scales),
                                 np.float32)[alive]
                med, best = timed(program(attend, layers, n_pages, steps),
                                  q, kc, vc, pt, pos, *scales)
            except Exception as e:                  # a compile refused
                print(f"KB {cname} | {vname}: FAILED "
                      f"{type(e).__name__}: {str(e)[:300]}", flush=True)
                continue
            ulps = float(np.abs(got - ref).max()
                         / (2.0 ** -8 * np.abs(ref).max()))
            ms = med / steps * 1e3
            out[f"{cname} | {vname}"] = {
                "ms_step": ms, "ms_step_best": best / steps * 1e3,
                "bound_ms": bound_ms, "pct": 100 * bound_ms / ms,
                "ulps": ulps, "live_tokens": tokens}
            print(f"KB {cname} | {vname}: {ms:.3f} ms a step (best "
                  f"{best / steps * 1e3:.3f}; HBM needs {bound_ms:.3f} "
                  f"for the live tokens: {100 * bound_ms / ms:.1f}%), "
                  f"{ulps:.2f} ulps of the gather", flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = "gpt_attention_kernel" + ("_int8" if a.int8 else "") \
        + (f"_ps{ps}" if ps != 16 else "") + ".json"
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
