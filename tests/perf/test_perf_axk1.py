"""The ``axk1`` architecture (A.X-K1: latent attention, a leading dense
layer, sigmoid-routed experts beside a shared one) against its plain
reference ``architectures/axk1_reference.py`` at a small size on the
CPU (``fixtures/axk1-nano.json``: hidden 64, 4 heads, latent 32 + 16,
16 experts in 4 groups of which top 4 of 2 groups, 8 held, 1 dense + 2
expert layers): the served arithmetic (paged prefill, then decode
through the latent pages) on logits; a control for every mechanism
(the reference with ONE left out has to fail the tolerance); the
shares of the experts adding up to the uncut layer; the functions that
count a decode step's bytes; the new readers on a hand-made run; and a
rehearsal of a cell of this architecture through ``run.py``.

TOLERANCE at this size (``logits_rel_tol`` 0.07, ``tie_eps`` 0.008 in
the fixture): over 12 seeds x 16 vectors bfloat16 against float32
``highest`` reads at most 0.050 (the next 0.032) wherever the
reference's selections clear their edges by 0.008 in the sigmoid score,
and 0.11-0.43 where one does not (program and reference then hold
different experts: a whole expert's part). Every mechanism left out
reads 0.23 or more. A hidden size of 64 rounds the router's scores far
coarser than 7168 does: the cell's own limits (0.1, 0.009) are the
configuration file's, read on the chip."""
import dataclasses
import json
import os

import numpy as np
import pytest

import perf_testlib as L

import perf_deployment
import perf_harness as H

CELL = "axk1-ep16-reason-offline"


def _conf(name="axk1-nano"):
    if name == "axk1-nano":
        return H.load_json(L.fixture("axk1-nano.json"))
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


def _rows(conf, seed):
    ck = conf["correct"]
    total = ck["prompt_tokens"] + ck["decode_steps"]
    rng = np.random.default_rng([seed, 77])
    return rng.integers(0, conf["vocab_size"],
                        (ck["rows"], total + 1)).astype(np.int32)


def _rel(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_prefill_then_decode_through_latent_pages_agree_on_logits(nano,
                                                                  seed):
    """System against reference on seeded weights: the paged prefill
    and cached decode steps (bfloat16, the up-projections absorbed)
    against the reference's full forward pass (float32), where the
    reference can decide its own selections."""
    import jax
    import jax.numpy as jnp

    conf, arch, cfg, _ref = nano
    ck = conf["correct"]
    params = perf_deployment.seeded_params(arch, cfg, seed, conf["init"])
    seqs = _rows(conf, seed)
    n_prompt, n_steps = ck["prompt_tokens"], ck["decode_steps"]
    total = n_prompt + n_steps
    got = arch.served_logits(_Engine(params, conf), cfg, seqs, n_prompt,
                             n_steps)
    from_program, forward, _ = arch.reference(cfg)
    weights = from_program(params)
    want = np.asarray(jax.jit(forward)(weights, jnp.asarray(
        seqs[:, :total])))
    dec = np.asarray(jax.jit(arch.decidable(cfg, conf))(
        weights, jnp.asarray(seqs[:, :total])))
    assert dec.shape == (ck["rows"], total) and dec.mean() > 0.7
    for i, pos in ((0, n_prompt - 1), (n_steps, total - 1)):
        keep = dec[:, pos]
        assert keep.sum() >= 4
        assert _rel(got[i][keep], want[keep, pos]) <= ck["logits_rel_tol"]


@pytest.fixture(scope="module")
def uncut(nano):
    """Weights with ALL 16 experts, the program's share of them
    (experts 0-7), the reference's logits on both, and the hp."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mla_moe

    conf, arch, cfg, ref = nano
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32,
                                param_dtype=jnp.float32)
    whole = mla_moe.init_params(
        jax.random.PRNGKey(4), dataclasses.replace(
            cfg32, experts_held=cfg.n_routed), {"embed": 1.0})
    hp = dict(arch.hyper(cfg32), weights_offset=0)
    tokens = jnp.asarray(_rows(conf, 4)[:, :-1])
    weights = ref.from_program(whole)
    logits = np.asarray(ref.forward(weights, tokens, hp))
    return cfg32, whole, weights, hp, tokens, logits


def test_the_programs_share_is_the_references_share(nano, uncut):
    """The reference given all 16 experts and told 8 are held agrees
    with the program that holds only those 8 (float32 on both sides:
    no rounding, no near-tie)."""
    import jax

    from ray_tpu.models import mla_moe

    cfg32, whole, _w, _hp, tokens, logits = uncut
    held = jax.tree_util.tree_map(lambda a: a, whole)
    for p in held["layers"]:
        if "experts" in p:
            p["experts"] = {k: v[:cfg32.experts_held]
                            for k, v in p["experts"].items()}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(mla_moe.forward(held, tokens, cfg32))
    assert _rel(got, logits) < 1e-4


def test_every_mechanism_is_listed(nano):
    assert nano[3].MECHANISMS == (
        "rotary", "yarn_blend", "mscale", "latent_norm", "sigmoid",
        "group_limit", "norm_topk", "route_scale", "shared_expert",
        "absent_experts_left_out")


@pytest.mark.parametrize("mechanism", [
    "rotary", "yarn_blend", "mscale", "latent_norm", "sigmoid",
    "group_limit", "norm_topk", "route_scale", "shared_expert",
    "absent_experts_left_out"])
def test_a_mechanism_left_out_fails_the_tolerance(nano, uncut, mechanism):
    """PR 28's lesson: an initialisation that hides a mechanism passes
    a reference WITHOUT it. The reference with ONE mechanism left out
    (rotary on q_r/k_r; the YaRN blend; m^2 in the scale; the latent's
    RMSNorm; sigmoid -> softmax; group limiting -> plain top k;
    norm_topk_prob; the factor 2.5; the shared expert; the absent
    experts' part added back) is off by more than the agreement test
    allows, after prefill's position and after decode's."""
    conf, _arch, _cfg, ref = nano
    _cfg32, _whole, weights, hp, tokens, logits = uncut
    ck = conf["correct"]
    off = np.asarray(ref.forward(weights, tokens, hp, without=mechanism))
    for pos in (ck["prompt_tokens"] - 1, tokens.shape[1] - 1):
        assert _rel(off[:, pos], logits[:, pos]) > 2 * ck["logits_rel_tol"]


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(nano, uncut):
    """The share tied to the model: the parts that BOTH shares of the
    16 experts give (experts 0-7 and 8-15, each through the program's
    dropless layer), with the shared expert counted once, add up to
    the uncut reference's layer output."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import moe

    _conf_, _arch, _cfg, ref = nano
    cfg32, whole, weights, hp, _tokens, _logits = uncut
    layer = cfg32.n_dense              # the first expert layer
    h = jnp.asarray(np.random.default_rng(5).normal(
        size=(40, cfg32.d_model)), jnp.float32)
    want, _ = ref.expert_layer(
        h, weights, layer, dict(hp, experts_held=cfg32.n_routed))
    p = whole["layers"][layer]
    half = cfg32.n_routed // 2
    with jax.default_matmul_precision("highest"):
        parts = [moe.dropless_moe(
            h, p["router"]["kernel"],
            {k: v[off:off + half] for k, v in p["experts"].items()},
            experts_held=half, expert_offset=off, n_group=cfg32.n_group,
            topk_group=cfg32.topk_group, top_k=cfg32.top_k,
            norm_topk=True, route_scale=cfg32.route_scale,
            dtype=jnp.float32, block_rows=8) for off in (0, half)]
        total = parts[0][0] + parts[1][0] \
            + moe.gated_ffn(h, p["shared"], jnp.float32)
    assert _rel(np.asarray(total), np.asarray(want)) < 1e-5
    # every choice landed on one share or the other, none on both
    assert int(parts[0][1][1] + parts[1][1][1]) == 40 * cfg32.top_k
    # and one share alone is NOT the layer
    one = parts[0][0] + moe.gated_ffn(h, p["shared"], jnp.float32)
    assert _rel(np.asarray(one), np.asarray(want)) > 0.1


def test_decidable_follows_tie_eps(nano, uncut):
    import jax.numpy as jnp

    conf, arch, cfg, _ref = nano
    _c, _w, weights, _hp, tokens, _l = uncut

    def share(eps):
        c = dict(conf, correct=dict(conf["correct"], tie_eps=eps))
        return float(jnp.mean(arch.decidable(cfg, c)(weights, tokens)))

    assert share(1e-9) == 1.0 and share(10.0) == 0.0
    assert share(0.004) > share(0.02) > share(0.1)


# ---- the cell's configuration, and what its readers count

PUBLISHED = {"hidden_size": 7168, "intermediate_size": 18432,
             "moe_intermediate_size": 2048, "num_attention_heads": 64,
             "q_lora_rank": 1536, "kv_lora_rank": 512,
             "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
             "v_head_dim": 128, "num_experts_per_tok": 8, "n_group": 8,
             "topk_group": 4, "router_width": 192,
             "first_k_dense_replace": 1, "n_shared_experts": 1,
             "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
             "max_position_embeddings": 131072, "rope_theta": 10000}


def test_the_configuration_keeps_every_published_width():
    conf = _conf("a.x-k1-ep16-serve")
    for key, value in PUBLISHED.items():
        assert conf[key] == value, key
    assert conf["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (conf["scoring_func"], conf["topk_method"],
            conf["norm_topk_prob"]) == ("sigmoid", "none", True)
    assert conf["cut"] == {
        "num_hidden_layers": {"published": 61, "held": 7},
        "n_routed_experts": {"published": 192, "held": 12},
        "vocab_size": {"published": 163840, "held": 20480}}
    assert conf["cut_stands_for"]["chips_sharing_a_layer"] == 16
    # the guide's floors: a leading dense layer and at least four of
    # those that follow, at least 8 routed experts, an eighth of the ids
    assert conf["num_hidden_layers"] - conf["first_k_dense_replace"] >= 4
    assert conf["n_routed_experts"] >= 8
    assert conf["vocab_size"] * 8 >= 163840
    assert conf["numerics"] == {"param_dtype": "bfloat16",
                                "compute_dtype": "bfloat16",
                                "kv_dtype": "bfloat16"}
    mix = H.load_mix("reason-offline")
    assert mix["clients"] == 2 * conf["engine"]["slots"] == 256
    assert mix["fill_pages"] >= conf["engine"]["n_pages"]
    assert (mix["prompt"], mix["answer"]) == (
        {"dist": "uniform", "min": 128, "max": 512},
        {"dist": "uniform", "min": 384, "max": 768})
    assert max(conf["engine"]["prompt_buckets"]) >= mix["prompt"]["max"]
    assert mix["prompt"]["max"] + mix["answer"]["max"] \
        <= conf["engine"]["max_len"]


def test_a_decode_steps_bytes_are_the_programs_weights_by_the_counter():
    """4.84 G parameters at the published widths, of which a step
    multiplies by all but the embedding table; of the routed experts by
    those the COUNTER says were touched, never 12 by assumption."""
    import jax

    conf = _conf("a.x-k1-ep16-serve")
    arch = H.load_architecture(conf)
    shapes = arch.param_shapes(arch.model_cfg(conf))
    n = sum(int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(shapes))
    assert n == 4_841_331_712
    table = conf["vocab_size"] * conf["hidden_size"]
    expert = 3 * conf["hidden_size"] * conf["moe_intermediate_size"]
    delta = {"moe_steps": 600, "moe_experts_touched_sum": 600 * 12,
             "moe_tokens_here_sum": 600 * 64, "moe_expert_peak_sum": 6000}
    assert arch.decode_step_bytes(conf, 2, 2, 0, delta) == 2 * (n - table)
    live = 80_000
    latents = live * 7 * 576 * 2
    assert arch.decode_step_bytes(conf, 2, 2, live, delta) \
        == 2 * (n - table) + latents
    # 11.5 of 12 touched a layer: half an expert's 88 MB less, a layer
    delta["moe_experts_touched_sum"] = 600 * 11.5
    assert arch.decode_step_bytes(conf, 2, 2, 0, delta) \
        == 2 * (n - table) - 6 * 0.5 * expert * 2
    # a program without the counters: no routed expert is assumed
    assert arch.decode_step_bytes(conf, 2, 2, 0, {}) \
        == 2 * (n - table) - 6 * 12 * expert * 2
    bytes_, flops = arch.moe_experts_cost(conf, 2, delta)
    assert bytes_ == 6 * 11.5 * expert * 2
    assert flops == 6 * 64 * 2 * expert
    assert arch.moe_experts_cost(conf, 2, {}) is None
    assert arch.mla_attention_cost(conf, 2, live) == (
        latents, 7 * live * 64 * 2 * (576 + 512))


STEP_MS, CHUNK_S = 25.0, 2.0
RUN = {
    "conf": None, "peaks": {"hbm_bytes_per_s": 819e9,
                            "bf16_flops_per_s": 197e12},
    "stats_delta": {"moe_steps": 9600, "moe_experts_touched_sum": 115_000,
                    "moe_tokens_here_sum": 610_000,
                    "moe_expert_peak_sum": 105_600},
    "trace_mid": 10.0,
    "rows": [{"prompt_len": 300, "slices": [[5.0, 1], [9.0, 199]],
              "end": None}] * 100,
    "trace": {"busy_s": 4.0, "scopes": {
        "while/body/closed_call/decode_step/moe.experts/while/body/"
        "dot_general": 0.8,
        "moe.experts/while/body/dot_general": 0.1,
        "while/body/closed_call/decode_step/moe.route/sort": 0.2,
        "while/body/closed_call/decode_step/mla.attention/gather": 0.6,
        "mla.prefill/dot_general": 0.05, "other": 0.3},
        "programs": {"jit_decode_chunk_slots_paged(3)": {
            "launches": 10.0, "seconds": CHUNK_S}},
        "launches_by_host": {"engine.py:_dispatch_chunk": {
            "launches": 9, "seconds": 1.8, "programs": {
                "jit_decode_chunk_slots_paged(3)": {
                    "launches": 9, "seconds": 9 * 8 * STEP_MS / 1e3}}}}},
}


def test_the_new_readers_on_a_hand_made_run():
    conf = _conf("a.x-k1-ep16-serve")
    arch = H.load_architecture(conf)
    run = dict(RUN, conf=conf)
    read = {m["name"]: H.load_reader(m["name"]).read(run)
            for m in L.benchmark()["per_layer"]
            if m.get("workloads") == [CELL]}
    assert len(read) == 8
    assert read["moe_experts_share_pct"] == pytest.approx(100 * 0.9 / 4.0)
    assert read["moe_route_share_pct"] == pytest.approx(100 * 0.2 / 4.0)
    assert read["mla_attn_share_pct"] == pytest.approx(100 * 0.6 / 4.0)
    touched = 115_000 / 9600
    assert read["moe_experts_touched_pct"] == pytest.approx(
        100 * touched / 12)
    assert read["moe_tokens_per_expert"] == pytest.approx(610 / 115)
    assert read["moe_imbalance"] == pytest.approx(
        (105_600 / 9600) / (610 / 115))
    # a scope's seconds a step: its share of the chunk program's time
    # in the slice, of the step's time; the decode program's rows only
    step_s = STEP_MS / 1e3
    cost = arch.moe_experts_cost(conf, 2, run["stats_delta"])
    assert read["moe_experts_roofline_pct"] == pytest.approx(
        100 * (cost[0] / 819e9) / (0.8 * step_s / CHUNK_S))
    live = 100 * 500
    cost = arch.mla_attention_cost(conf, 2, live)
    assert read["mla_attn_roofline_pct"] == pytest.approx(
        100 * max(cost[0] / 819e9, cost[1] / 197e12)
        / (0.6 * step_s / CHUNK_S))
    assert all(0 < v < 100 for k, v in read.items() if k.endswith("pct"))
    # the whole step's share joins through the architecture's count
    whole = H.load_reader("decode_roofline_pct.sat").read(run)
    assert whole == pytest.approx(100 * arch.decode_step_bytes(
        conf, 2, 2, live, run["stats_delta"]) / 819e9 / step_s)
    # a program without the scopes or the counters (the parent): nothing
    bare = dict(run, stats_delta={}, trace=dict(
        run["trace"], scopes={"while/body/dot_general": 1.0}))
    for name in read:
        assert H.load_reader(name).read(bare) is None, name


def test_a_cell_of_this_architecture_runs_through_run_py(tmp_path):
    """A rehearsal: the fixture's configuration and the planted
    tree's closed-loop mix, added to a copy and joined to every list
    the cell is in; one traced run through ``run.py``. The counters'
    readers read the window; what reads a device plane is left out."""
    cell = L.cell("axk1-nano-batch", "axk1-nano", "axk1-nano-batch")
    new = [m for m in L.benchmark()["per_layer"]
           if m.get("workloads") == [CELL]]
    root = L.copy_with_additions(
        tmp_path, configs=[("axk1-nano", L.fixture("axk1-nano.json"))],
        mixes=[("axk1-nano-batch", L.fixture("nano-batch.json"))],
        cells=[cell], join={"axk1-nano-batch": CELL})
    bench = L.benchmark(root)
    for m in bench["per_layer"]:
        if m["name"] in {n["name"] for n in new}:
            assert m["workloads"] == [CELL, "axk1-nano-batch"]
    rc, out, err = L.run_copy(
        root, "--workload", "axk1-nano-batch", "--seed",
        str(2 ** 31 + 37), "--seconds", "4", "--trace", "1",
        "--rehearsal", timeout=600)
    assert rc == 0, (out[-5:], err[-3000:])
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert 0 < got["moe_experts_touched_pct"] <= 100
    assert got["moe_tokens_per_expert"] >= 1
    assert got["moe_imbalance"] >= 1
    assert got["compiles_in_window.sat"] == 0
    assert not {"moe_experts_roofline_pct", "mla_attn_roofline_pct",
                "decode_roofline_pct.sat"} & set(got)
    setup = json.loads(next(ln for ln in out
                            if ln.startswith("SETUP "))[6:])
    vectors = setup["reference_vectors"]
    assert vectors["compared"] >= vectors["needed"] == 4
    assert all(c["rel"] is None or c["rel"] <= c["tol"]
               for c in setup["reference"])
    served = setup["served_check"]
    assert served["hit_after_eviction"] and served["reference"]["ok"]
    assert served["reference"]["control_max_gap"] \
        > served["reference"]["control_margin"]
