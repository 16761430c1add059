"""The ``falcon_h1`` architecture (Falcon-H1-34B-Instruct: a Mamba-2
mixer whose state lives per slot BESIDE rotary grouped-query attention
over key/value pages in every layer, a dense gated MLP, constant
multipliers on every branch) against its plain reference
``architectures/falcon_h1_reference.py`` at a small size on the CPU
(``fixtures/falcon_h1-nano.json``: hidden 64, 4 query / 2 KV heads of
16, 4 state-space heads of 16 over a 32-wide state in 2 groups, a
width-4 convolution with bias, chunks of 16, two layers): the served
arithmetic (paged prefill in the chunked form, then decode through the
pages and the per-slot state) on logits; the chunked form against the
token-at-a-time recurrence; a control for every mechanism (the
reference with ONE left out has to fail the tolerance); the functions
that count a decode step's bytes; the new readers on a hand-made run;
and a rehearsal of a cell of this architecture through ``run.py``.

TOLERANCE at this size. The fixture's numerics are FLOAT32: program
and reference, two implementations of one arithmetic, agree to 1e-5 of
the largest logit (``logits_rel_tol`` 0.001), and every mechanism left
out reads 0.15 or more. The model makes no discrete choice, so in
bfloat16 (``BF16``, below) the same comparison has no tail: every
vector reads under 0.06 at hidden 64; the cell's own limit is the
configuration file's, read on the chip at the published widths."""
import json

import numpy as np
import pytest

import perf_testlib as L

import perf_deployment
import perf_harness as H

CELL = "fh1-34b-reason-offline"
CONFIG = "falcon-h1-34b-serve"
#: the readers this architecture brought
OWN = ("ssm_state_share_pct", "ssm_state_roofline_pct",
       "ssm_proj_share_pct", "ssm_prefill_share_pct",
       "hgqa_attn_share_pct", "hgqa_attn_roofline_pct",
       "hybrid_mlp_share_pct", "lm_head_share_pct", "ssm_state_hbm_pct")


def _conf(name="falcon_h1-nano"):
    if name == "falcon_h1-nano":
        return H.load_json(L.fixture("falcon_h1-nano.json"))
    return H.load_config(next(c for c in L.benchmark()["configs"]
                              if c["name"] == name))


class _Engine:
    """What ``served_logits`` reads of an engine."""

    def __init__(self, params, conf):
        self.params = params
        self.page_size = conf["engine"]["page_size"]
        self.prompt_buckets = conf["engine"]["prompt_buckets"]
        self.kv_dtype, self.attn_kernel = "fp", "gather"


@pytest.fixture(scope="module")
def nano():
    conf = _conf()
    arch = H.load_architecture(conf)
    return conf, arch, arch.model_cfg(conf), arch.plain_reference()


def _seeded(arch, cfg, conf, seed):
    return arch.with_init_means(perf_deployment.seeded_params(
        arch, cfg, seed, conf["init"]), conf["init"])


def _rows(conf, seed):
    ck = conf["correct"]
    total = ck["prompt_tokens"] + ck["decode_steps"]
    rng = np.random.default_rng([seed, 77])
    return rng.integers(0, conf["vocab_size"],
                        (ck["rows"], total + 1)).astype(np.int32)


def _rel(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


#: the fixture at the cell's numerics, and what every vector stays under
BF16 = {"numerics": {"param_dtype": "bfloat16",
                     "compute_dtype": "bfloat16", "kv_dtype": "bfloat16",
                     "state_dtype": "float32"}, "logits_rel_tol": 0.06}


@pytest.mark.parametrize("seed,numerics", [
    (1, "float32"), (2, "float32"), (3, "float32"),
    (1, "bfloat16"), (2, "bfloat16")])
def test_prefill_then_decode_through_pages_and_state_agree_on_logits(
        nano, seed, numerics):
    """System against reference on seeded weights: the paged prefill
    (23 tokens in a bucket of 64: a multiple of neither the chunk of 16,
    the page of 8 nor the bucket; the chunked form, the state and the
    tail of the LAST token) and cached decode steps (the recurrence on
    the slot's state, rotary attention over pages) against the
    reference's full forward pass one token at a time, float32
    ``highest``, at EVERY row and both places."""
    import jax
    import jax.numpy as jnp

    conf, arch, cfg, _ref = nano
    tol = conf["correct"]["logits_rel_tol"]
    if numerics == "bfloat16":
        conf = dict(conf, numerics=BF16["numerics"])
        cfg, tol = arch.model_cfg(conf), BF16["logits_rel_tol"]
    ck = conf["correct"]
    params = _seeded(arch, cfg, conf, seed)
    seqs = _rows(conf, seed)
    n_prompt, n_steps = ck["prompt_tokens"], ck["decode_steps"]
    total = n_prompt + n_steps
    got = arch.served_logits(_Engine(params, conf), cfg, seqs, n_prompt,
                             n_steps)
    from_program, forward, _ = arch.reference(cfg)
    want = np.asarray(jax.jit(forward)(from_program(params), jnp.asarray(
        seqs[:, :total])))
    assert not hasattr(arch, "decidable")       # nothing is left out
    for i, pos in ((0, n_prompt - 1), (n_steps, total - 1)):
        assert _rel(got[i], want[:, pos]) <= tol, (i, numerics)


@pytest.fixture(scope="module")
def plain(nano):
    """Float32 weights drawn under the file's ``init``, the reference's
    inputs and its logits on them."""
    import jax.numpy as jnp

    conf, arch, cfg, ref = nano
    params = _seeded(arch, cfg, conf, 4)
    hp = arch.hyper(cfg)
    tokens = jnp.asarray(_rows(conf, 4)[:, :-1])
    weights = ref.from_program(params)
    return params, weights, hp, tokens, np.asarray(
        ref.forward(weights, tokens, hp))


@pytest.mark.parametrize("length", [16, 33, 128, 147])
def test_the_chunked_form_is_the_recurrence(nano, plain, length):
    """The program's whole-sequence pass (the SSD form in chunks of 16
    from a zero state) against the reference's token-at-a-time
    recurrence: at one whole chunk, across a chunk's boundary, at many
    chunks and at a length that is a multiple of nothing (33, 147:
    the last chunk is padded and its padding advances nothing)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import ssm_hybrid

    _conf_, _arch, cfg, ref = nano
    params, weights, hp, _t, _l = plain
    tokens = jnp.asarray(np.random.default_rng(length).integers(
        0, cfg.vocab_size, (2, length)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ssm_hybrid.forward(params, tokens, cfg))
    assert _rel(got, np.asarray(ref.forward(weights, tokens, hp))) < 1e-4


MECHANISMS = (
    "decay", "dt_bias", "d_skip", "short_conv", "conv_bias", "gate_z",
    "norm_groups", "head_groups", "mup_z", "mup_x", "mup_B", "mup_C",
    "mup_dt", "ssm_in_multiplier", "ssm_out_multiplier", "key_multiplier",
    "rotary", "attention_out_multiplier", "mlp_gate_multiplier",
    "mlp_down_multiplier", "embedding_multiplier", "lm_head_multiplier")


def test_every_mechanism_is_listed(nano):
    assert nano[3].MECHANISMS == MECHANISMS


@pytest.mark.parametrize("mechanism", MECHANISMS)
def test_a_mechanism_left_out_fails_the_tolerance(nano, plain, mechanism):
    """PR 28's lesson: an initialisation that hides a mechanism passes
    a reference WITHOUT it. The reference with ONE mechanism left out
    (the decay: a = 1; ``dt_bias``; the skip ``D``; the convolution;
    its bias; the gate ``z``; the norm's groups: one group; the
    head-to-group map: every head on group 0; each of the five
    ``ssm_multipliers`` and the multipliers on the branches' inputs and
    outputs; rotary) is off by more than the agreement test allows,
    after prefill's position and after decode's, under the file's
    ``init``."""
    conf, _arch, _cfg, ref = nano
    _params, weights, hp, tokens, logits = plain
    ck = conf["correct"]
    off = np.asarray(ref.forward(weights, tokens, hp, without=mechanism))
    for pos in (ck["prompt_tokens"] - 1, tokens.shape[1] - 1):
        assert _rel(off[:, pos], logits[:, pos]) > 0.15 \
            > 2 * BF16["logits_rel_tol"] > ck["logits_rel_tol"]


def test_the_init_spreads_the_decay_and_the_scores(nano, plain):
    """``init.why``: ``dt_bias`` and ``A_log`` are drawn around means
    that spread the step size over 0.001-0.3 and the decay over (0.2,
    0.999), not around e^-ln2; with ``key_mult`` in the draw the
    attention's scores are a few wide, so a softmax over 32 keys is far
    from uniform. A decay pinned at 1 or a uniform softmax would hide
    its mechanism."""
    import jax.numpy as jnp

    from ray_tpu.models import ssm_hybrid

    _conf_, _arch, cfg, _ref = nano
    params = plain[0]
    p = params["layers"][1]
    h = jnp.asarray(np.random.default_rng(0).normal(
        size=(200, cfg.d_model)), jnp.float32)
    _z, _xBC, dt, g = ssm_hybrid._ssm_proj(h, p, cfg)
    lo, hi = np.percentile(np.asarray(dt).ravel(), [5, 95])
    assert 0.0005 < lo < 0.01 and 0.05 < hi < 0.6
    lo, mid, hi = np.percentile(np.exp(np.asarray(g)).ravel(), [5, 50, 95])
    assert 0.01 < lo < 0.8 < mid < 0.99 < hi < 1.0    # four heads' A
    pos = jnp.arange(200)
    q, k, _v = ssm_hybrid._attn_qkv(h, p, pos, cfg)
    scores = np.einsum("qhd,khd->hqk", np.asarray(q)[:32, :2],
                       np.asarray(k)[:32]) * cfg.head_dim ** -0.5
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    entropy = -(probs * np.log(probs + 1e-30)).sum(-1).mean()
    assert 1.0 < scores.std() < 6.0 and entropy < np.log(32) - 0.8


# ---- the cell's configuration, and what its readers count

def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog row's ``config`` at the file's top
    level under its own name, the depth alone cut."""
    conf = _conf(CONFIG)
    published = {
        "attention_bias": False, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 21504,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
        "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
        "mamba_n_groups": 2, "mamba_n_heads": 32,
        "mamba_norm_before_gate": False, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_use_mlp": True,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "model_type": "falcon_h1", "num_attention_heads": 20,
        "num_key_value_heads": 4, "num_logits_to_keep": 1,
        "projectors_bias": False, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 100000000000,
        "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
        "tie_word_embeddings": False, "vocab_size": 261120}
    for key, value in published.items():
        assert conf[key] == value, key
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["cut"] == {"num_hidden_layers": {"published": 72,
                                                 "held": 5}}
    assert conf["cut_stands_for"]["chips_sharing_a_layer"] == 1
    # the guide's floor: a whole period (one layer) and four more
    assert conf["num_hidden_layers"] >= 5
    assert conf["numerics"] == {
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
        "kv_dtype": "bfloat16", "state_dtype": "float32"}
    text = " ".join(conf["assumed"])
    for word in ("mamba_use_mlp", "groups", "z | x | B | C | dt",
                 "state_dtype", "random"):
        assert word in text, word
    assert "tie_eps" not in conf["correct"]          # nothing left out
    eng = conf["engine"]
    assert eng["prefix_cache"] is False and eng["attn_kernel"] == "gather"
    mix = H.load_mix("reason-offline")
    assert mix["clients"] == 2 * eng["slots"]
    assert max(eng["prompt_buckets"]) >= mix["prompt"]["max"]
    longest = mix["prompt"]["max"] + mix["answer"]["max"]
    assert longest <= eng["max_len"]
    # the longest request in every lane fits the pool: no lane parks
    assert eng["slots"] * longest <= eng["n_pages"] * eng["page_size"]


def test_the_configuration_holds_to_its_own_statement():
    entry = next(c for c in L.benchmark()["configs"] if c["name"] == CONFIG)
    conf = _conf(CONFIG)
    L.check_configuration(entry, conf, H.load_architecture(conf))
    nano = _conf()
    L.check_configuration(
        {"reduced": nano["reduced"], "source": nano["source"]["url"]},
        nano, H.load_architecture(nano))


def test_a_decode_steps_bytes_are_the_programs_weights_by_the_counters():
    """4.82 G parameters at the published widths and five layers, of
    which a step multiplies by all but the embedding table; the state
    of the lanes the COUNTER says were live, once in and once out in
    float32, in EVERY layer; the live tokens' keys and values in every
    layer."""
    import jax

    conf = _conf(CONFIG)
    arch = H.load_architecture(conf)
    cfg = arch.model_cfg(conf)
    shapes = arch.param_shapes(cfg)
    n = sum(int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(shapes))
    layer = (5120 * (2560 + 512 + 512) + 2560 * 5120      # attention
             + 5120 * 9248 + 4096 * 5120                  # in / out proj
             + 5 * 5120 + 3 * 32 + 4096                   # conv, gates, norm
             + 3 * 5120 * 21504 + 2 * 5120)               # MLP, two norms
    table = 261120 * 5120
    # the table and the head as 12 row blocks: no leaf larger than an
    # MLP matrix, so the seeded fill's float32 draw of a leaf is 0.46
    # GB of temporaries, not 5.35 (PERF.md section 6, PR 52)
    assert len(shapes["embed"]["kernel"]) == len(
        shapes["head"]["kernel"]) == arch.vocab_blocks(cfg) == 12
    assert max(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) == 5120 * 21760
    assert layer == 430_120_032
    assert n == 5 * layer + 2 * table + 5120 == 4_824_474_080
    chunk = conf["engine"]["chunk"]
    delta = {"dispatches": 100, "state_lanes_sum": 0}
    assert arch.decode_step_bytes(conf, 2, 2, 0, delta) == 2 * (n - table)
    state = 32 * 128 * 256 * 4
    assert state == 4_194_304
    delta["state_lanes_sum"] = 100 * chunk * 126       # 126 of 128 live
    assert arch.state_lanes_per_step(conf, delta) == 126
    assert arch.decode_step_bytes(conf, 2, 2, 0, delta) \
        == 2 * (n - table) + 126 * 5 * state * 2
    live = 80_000
    kv = live * 5 * 2048                   # 4 x 128 x 2 values x 2 B
    assert arch.decode_step_bytes(conf, 2, 2, live, delta) \
        == 2 * (n - table) + 126 * 5 * state * 2 + kv
    # a program without the counter: no lane is assumed
    assert arch.decode_step_bytes(conf, 2, 2, 0, {}) == 2 * (n - table)
    assert arch.ssm_state_cost(conf, delta) == (
        126 * 5 * state * 2, 126 * 5 * 32 * 128 * 256 * 5)
    assert arch.ssm_state_cost(conf, {"dispatches": 100}) is None
    assert arch.ssm_state_cost(conf, {"state_lanes_sum": 5}) is None
    assert arch.hgqa_attention_cost(conf, 2, live) == (
        kv, live * 5 * 20 * 2 * (128 + 128))
    # what the engine's one description says, to the byte
    from ray_tpu.models import ssm_hybrid

    spec = ssm_hybrid.cache_spec(cfg)
    assert spec.bytes_per_page(16) == 16 * 5 * 2048
    assert spec.bytes_per_slot() == 5 * (state + 3 * 5120 * 2)


STEP_MS, CHUNK_S = 25.0, 2.0
RUN = {
    "conf": None, "peaks": {"hbm_bytes_per_s": 819e9,
                            "bf16_flops_per_s": 197e12,
                            "hbm_bytes": 2 ** 34},
    "stats_delta": {"dispatches": 200, "state_lanes_sum": 200 * 8 * 127},
    "stats_after": {"state_bytes": 128 * 5 * (4_194_304 + 30_720)},
    "trace_mid": 10.0,
    "rows": [{"prompt_len": 300, "slices": [[5.0, 1], [9.0, 299]],
              "end": None}] * 127,
    "trace": {"busy_s": 4.0, "scopes": {
        "while/body/closed_call/decode_step/ssm.state/mul": 0.9,
        "while/body/closed_call/decode_step/ssm.state/reduce_sum": 0.5,
        "while/body/closed_call/decode_step/ssm.proj/dot_general": 0.3,
        "while/body/closed_call/decode_step/hgqa.attention/"
        "gqa_attention/pallas_call": 0.2,
        "while/body/closed_call/decode_step/hybrid.mlp/dot_general": 0.8,
        "while/body/closed_call/decode_step/lm.head/dot_general": 0.6,
        "hybrid.mlp/dot_general": 0.1, "lm.head/dot_general": 0.02,
        "ssm.proj/dot_general": 0.04,
        "ssm.prefill/while/body/dot_general": 0.12,
        "hgqa.prefill/dot_general": 0.05, "other": 0.3},
        "programs": {"jit_decode_chunk_slots_paged(3)": {
            "launches": 10.0, "seconds": CHUNK_S}},
        "launches_by_host": {"engine.py:_dispatch_chunk": {
            "launches": 9, "seconds": 1.8, "programs": {
                "jit_decode_chunk_slots_paged(3)": {
                    "launches": 9, "seconds": 9 * 8 * STEP_MS / 1e3}}}}},
}


def test_the_readers_on_a_hand_made_run():
    """The nine readers this architecture brought are listed for its
    cell alone, and read a hand-made run as their files say."""
    conf = _conf(CONFIG)
    arch = H.load_architecture(conf)
    run = dict(RUN, conf=conf)
    listed = {m["name"]: m for m in L.benchmark()["per_layer"]}
    for name in OWN:
        assert listed[name]["workloads"] == [CELL], name
        assert listed[name]["moves"] == "out_tokens_per_s"
    read = {name: H.load_reader(name).read(run) for name in OWN}
    assert read["ssm_state_share_pct"] == pytest.approx(100 * 1.4 / 4.0)
    assert read["ssm_proj_share_pct"] == pytest.approx(100 * 0.34 / 4.0)
    assert read["ssm_prefill_share_pct"] == pytest.approx(100 * 0.12 / 4)
    assert read["hgqa_attn_share_pct"] == pytest.approx(100 * 0.2 / 4.0)
    assert read["hybrid_mlp_share_pct"] == pytest.approx(100 * 0.9 / 4.0)
    assert read["lm_head_share_pct"] == pytest.approx(100 * 0.62 / 4.0)
    assert read["ssm_state_hbm_pct"] == pytest.approx(
        100 * 128 * 5 * 4_225_024 / 2 ** 34)
    # a scope's seconds a step: its share of the chunk program's time
    # in the slice, of the step's time; the decode program's rows only
    step_s = STEP_MS / 1e3
    cost = arch.ssm_state_cost(conf, run["stats_delta"])
    assert cost[0] == 127 * 5 * 4_194_304 * 2
    assert read["ssm_state_roofline_pct"] == pytest.approx(
        100 * (cost[0] / 819e9) / (1.4 * step_s / CHUNK_S))
    live = 127 * 600
    cost = arch.hgqa_attention_cost(conf, 2, live)
    assert read["hgqa_attn_roofline_pct"] == pytest.approx(
        100 * max(cost[0] / 819e9, cost[1] / 197e12)
        / (0.2 * step_s / CHUNK_S))
    assert all(0 < v < 100 for v in read.values())
    # the whole step's share joins through the architecture's count
    whole = H.load_reader("decode_roofline_pct.sat").read(run)
    assert whole == pytest.approx(100 * arch.decode_step_bytes(
        conf, 2, 2, live, run["stats_delta"]) / 819e9 / step_s)
    assert 0 < whole < 100
    # a program without the scopes or the counters (the parent): nothing
    bare = dict(run, stats_delta={}, stats_after={}, trace=dict(
        run["trace"], scopes={"while/body/dot_general": 1.0}))
    for name in OWN:
        assert H.load_reader(name).read(bare) is None, name


def test_a_cell_of_this_architecture_runs_through_run_py(tmp_path):
    """A rehearsal: the fixture's configuration (``prefix_cache``
    false: the check request's four answers are four whole prefills)
    and the planted tree's closed-loop mix, added to a copy and joined
    to every list the cell is in; one traced run through ``run.py``.
    The counters' readers read the window; what reads a device plane is
    left out."""
    cell = L.cell("fh1-nano-batch", "falcon_h1-nano", "fh1-nano-batch")
    root = L.copy_with_additions(
        tmp_path,
        configs=[("falcon_h1-nano", L.fixture("falcon_h1-nano.json"))],
        mixes=[("fh1-nano-batch", L.fixture("nano-batch.json"))],
        cells=[cell], join={"fh1-nano-batch": CELL})
    listed = {m["name"]: m.get("workloads")
              for m in L.benchmark(root)["per_layer"]}
    for name in OWN:
        assert listed[name] == [CELL, "fh1-nano-batch"]
    rc, out, err = L.run_copy(
        root, "--workload", "fh1-nano-batch", "--seed",
        str(2 ** 31 + 52), "--seconds", "4", "--trace", "1",
        "--rehearsal", timeout=600)
    assert rc == 0, (out[-5:], err[-3000:])
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["compiles_in_window.sat"] == 0
    assert {"slot_occupancy_pct.sat", "dispatches_per_token.sat"} <= set(got)
    # what reads a device plane or the chip's peaks is left out here
    assert not {"ssm_state_roofline_pct", "hgqa_attn_roofline_pct",
                "decode_roofline_pct.sat", "ssm_state_hbm_pct"} & set(got)
    setup = json.loads(next(ln for ln in out
                            if ln.startswith("SETUP "))[6:])
    vectors = setup["reference_vectors"]
    assert vectors["compared"] == vectors["needed"] == 16   # every one
    assert all(c["rel"] <= c["tol"] for c in setup["reference"])
    served = setup["served_check"]
    assert served["complete"] and served["reference"]["ok"]
    assert served["reference"]["left_out"] == 0
    assert not served["hit_fresh"] and not served["hit_after_eviction"]
    assert served["reference"]["control_max_gap"] \
        > served["reference"]["control_margin"]
