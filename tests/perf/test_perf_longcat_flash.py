"""The ``longcat_flash`` architecture (LongCat-Flash-Chat: two latent
attentions and two dense FFNs a layer beside a shortcut-connected
expert layer; a softmax router with a selection bias whose last scores
are identity experts) against its plain reference
``architectures/longcat_flash_reference.py`` at a small size on the CPU
(``fixtures/longcat_flash-nano.json``: hidden 64, 4 heads, ranks 48 /
32, two layers, a router 24 wide over 16 routed experts of which 8 are
held and 8 identity experts, top 4): the served arithmetic on logits at
the cell's numerics; the configuration held to its own statement and to
the published widths; the functions that count a decode step's bytes;
the nine readers on a hand-made run; and a rehearsal of a cell of this
architecture through ``run.py``. The mechanisms' controls at float32
and the shares adding up to the layer are ``tests/
test_serve_engine_scmoe.py``'s.

TOLERANCE at this size (bfloat16, the cell's numerics): where the
reference calls a position decidable (``tie_eps`` 0.004 in ``p + b``:
a score at the edge of the top 4 of 24 is near 0.07, and the bfloat16
program's scores differ from the reference's by up to 0.002) the
comparison reads 0.004-0.03 over seeds 1-6 x 16 vectors, and a
mechanism left out 0.1 or more (``test_serve_engine_scmoe.py``, at
float32): ``logits_rel_tol`` 0.07 as the other nano fixtures. The
cell's own limits are the configuration file's, read on the chip at
the published widths."""
import json

import numpy as np
import pytest

import perf_testlib as L

import perf_deployment
import perf_harness as H

CELL = "lcflash-ep32-reason-offline"
CONFIG = "longcat-flash-ep32-serve"
#: the readers this architecture brought
OWN = ("zmoe_experts_share_pct", "zmoe_route_share_pct",
       "zmoe_experts_roofline_pct", "zmoe_zero_choice_pct",
       "zmoe_tokens_per_expert", "zmoe_imbalance",
       "scmoe_dense_share_pct", "scmoe_attn_share_pct",
       "scmoe_attn_roofline_pct")


def _conf(name="longcat_flash-nano"):
    if name == "longcat_flash-nano":
        return H.load_json(L.fixture("longcat_flash-nano.json"))
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


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_prefill_then_decode_through_the_pages_agree_on_logits(nano, seed):
    """System against reference on seeded weights at the cell's
    numerics: the paged prefill (23 tokens in a bucket of 64, both
    attentions' latents into pages) and cached decode steps through the
    latent pages against the reference's full forward pass, float32
    ``highest``, where the reference can decide its own selections."""
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
    assert dec.shape == (ck["rows"], total) and 0.2 < dec.mean() < 1
    for i, pos in ((0, n_prompt - 1), (n_steps, total - 1)):
        keep = dec[:, pos]
        assert keep.any()
        rel = np.abs(got[i][keep] - want[keep, pos]).max() \
            / np.abs(want[keep, pos]).max()
        assert rel <= ck["logits_rel_tol"], (pos, rel)


def test_decidable_follows_tie_eps(nano):
    import jax.numpy as jnp

    conf, arch, cfg, ref = nano
    params = perf_deployment.seeded_params(arch, cfg, 4, conf["init"])
    weights = ref.from_program(params)
    tokens = jnp.asarray(_rows(conf, 4)[:, :-1])

    def share(eps):
        c = dict(conf, correct=dict(conf["correct"], tie_eps=eps))
        return float(jnp.mean(arch.decidable(cfg, c)(weights, tokens)))

    assert share(1e-9) == 1.0 and share(10.0) == 0.0
    assert share(0.001) > share(0.004) > share(0.02)


# ---- the cell's configuration, and what its readers count

#: the catalog row's ``config``, key for key, but for the three cut
PUBLISHED = {
    "attention_bias": False, "hidden_size": 6144, "ffn_hidden_size": 12288,
    "expert_ffn_hidden_size": 2048, "num_attention_heads": 64,
    "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
    "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity",
    "moe_topk": 12}
HELD = 5_172_749_312


def test_the_configuration_is_held_to_its_own_statement():
    import jax

    entry = next(c for c in L.benchmark()["configs"]
                 if c["name"] == CONFIG)
    conf = H.load_config(entry)
    arch = H.load_architecture(conf)
    L.check_configuration(entry, conf, arch)
    for key, value in PUBLISHED.items():
        assert conf[key] == value and type(conf[key]) is type(value), key
    assert conf["router_width"] == 512 + conf["zero_expert_num"] == 768
    assert conf["cut"] == {
        "num_layers": {"published": 28, "held": 4},
        "n_routed_experts": {"published": 512, "held": 16},
        "vocab_size": {"published": 131072, "held": 16384}}
    assert conf["cut_stands_for"]["chips_sharing_a_layer"] == 32
    assert conf["n_routed_experts"] * 32 == 512
    # the guide's floors: a whole period (one layer), four layers, at
    # least 8 routed experts, an eighth of the ids
    assert conf["num_layers"] >= 4 and conf["n_routed_experts"] >= 8
    assert conf["vocab_size"] * 8 >= 131072
    assert conf["numerics"] == {
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
        "kv_dtype": "bfloat16"}
    # the recount: the file's arithmetic and the program's own tree,
    # to the parameter (the architecture's count from the published
    # keys is held to it by the decode step's bytes, below)
    cfg = arch.model_cfg(conf)
    n = sum(int(np.prod(leaf.shape)) for leaf in
            jax.tree_util.tree_leaves(arch.param_shapes(cfg)))
    assert n == HELD
    assert f"{HELD:,}" in conf["cut_stands_for"]["how"]
    assert (cfg.q_gain, round(cfg.kv_gain, 4)) == (2.0, 3.4641)
    # the two factors are the model, not a switch: a file without
    # them states another model and is refused
    for key in ("mla_scale_q_lora", "mla_scale_kv_lora"):
        with pytest.raises(AssertionError):
            arch.model_cfg(dict(conf, **{key: False}))
    assert cfg.router_width == 768 and cfg.n_routed == 512
    text = " ".join(conf["assumed"])
    for word in ("softmax", "selection bias", "identity", "halves",
                 "sqrt(hidden_size / q_lora_rank)", "random"):
        assert word in text, word
    for mechanism in arch.plain_reference().MECHANISMS[:7]:
        assert mechanism in conf["init"]["why"], mechanism
    # the engine against the mix it is run under
    eng = conf["engine"]
    assert set(eng["why"]) >= {"slots", "n_pages", "prompt_buckets",
                               "attn_kernel", "moe_block_rows"}
    cell = next(w for w in L.benchmark()["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reason-offline", 1)
    mix = H.load_mix(cell["traffic"])
    assert mix["clients"] == 2 * eng["slots"] == 256
    assert mix["fill_pages"] >= eng["n_pages"]
    assert max(eng["prompt_buckets"]) >= mix["prompt"]["max"]
    assert mix["prompt"]["max"] + mix["answer"]["max"] <= eng["max_len"]
    longest = -(-(mix["prompt"]["max"] + mix["answer"]["max"])
                // eng["page_size"])
    assert eng["slots"] * longest <= eng["n_pages"]
    from ray_tpu.models import scmoe

    assert scmoe.kv_bytes_per_page(cfg, eng["page_size"]) \
        == 8 * 16 * 640 * 2 == 163_840


def test_a_decode_steps_bytes_are_the_programs_weights_by_the_counters():
    """A step multiplies by every held weight but the embedding table;
    of the routed experts by those the COUNTER says were touched; an
    identity expert holds nothing; the live tokens' latents in EIGHT
    attentions."""
    conf = _conf(CONFIG)
    arch = H.load_architecture(conf)
    table = conf["vocab_size"] * conf["hidden_size"]
    expert = 3 * 6144 * 2048
    steps = 150
    delta = {"moe_steps": steps * 4, "moe_experts_touched_sum": 600 * 16,
             "moe_tokens_here_sum": 600 * 32, "moe_expert_peak_sum": 3000,
             "moe_tokens_sum": 600 * 128,
             "moe_zero_choices_sum": 600 * 128 * 4}
    assert arch.decode_step_bytes(conf, 2, 2, 0, delta) \
        == 2 * (HELD - table)
    live = 90_000
    latents = live * 8 * 576 * 2
    assert arch.decode_step_bytes(conf, 2, 2, live, delta) \
        == 2 * (HELD - table) + latents
    # 14.5 of 16 touched a layer: one and a half experts' 75 MB less
    delta["moe_experts_touched_sum"] = 600 * 14.5
    assert arch.decode_step_bytes(conf, 2, 2, live, delta) \
        == 2 * (HELD - table) + latents - 4 * 1.5 * expert * 2
    # a program without the counters: no expert is assumed
    assert arch.decode_step_bytes(conf, 2, 2, 0, {}) \
        == 2 * (HELD - table) - 4 * 16 * expert * 2
    bytes_, flops = arch.moe_experts_cost(conf, 2, delta)
    assert bytes_ == 4 * 14.5 * expert * 2
    assert flops == 4 * 32 * 2 * expert
    assert arch.moe_experts_cost(conf, 2, {}) is None
    assert arch.mla_attention_cost(conf, 2, live) == (
        latents, live * 8 * 64 * 2 * (576 + 512))


STEP_MS, CHUNK_S = 20.0, 1.6
RUN = {
    "conf": None, "peaks": {"hbm_bytes_per_s": 819e9,
                            "bf16_flops_per_s": 197e12,
                            "hbm_bytes": 2 ** 34},
    "stats_delta": {"moe_steps": 4000, "moe_experts_touched_sum": 56_000,
                    "moe_tokens_here_sum": 126_000,
                    "moe_expert_peak_sum": 24_000,
                    "moe_tokens_sum": 500_000,
                    "moe_zero_choices_sum": 2_010_000},
    "trace_mid": 10.0,
    "rows": [{"prompt_len": 300, "slices": [[5.0, 1], [9.0, 399]],
              "end": None}] * 125,
    "trace": {"busy_s": 4.0, "scopes": {
        "while/body/closed_call/decode_step/mla.attention/"
        "latent_attention/pallas_call": 0.6,
        "while/body/closed_call/decode_step/scmoe.dense/dot_general": 1.2,
        "scmoe.dense/dot_general": 0.2,
        "while/body/closed_call/decode_step/moe.experts/while/body/"
        "dot_general": 0.8,
        "moe.experts/while/body/dot_general": 0.1,
        "while/body/closed_call/decode_step/moe.route/sort": 0.2,
        "while/body/closed_call/decode_step/moe.zero/mul": 0.04,
        "mla.prefill/dot_general": 0.05, "other": 0.3},
        "programs": {"jit_decode_chunk_slots_paged(3)": {
            "launches": 10.0, "seconds": CHUNK_S}},
        "launches_by_host": {"engine.py:_dispatch_chunk": {
            "launches": 9, "seconds": 1.5, "programs": {
                "jit_decode_chunk_slots_paged(3)": {
                    "launches": 9, "seconds": 9 * 8 * STEP_MS / 1e3}}}}},
}


def test_the_readers_on_a_hand_made_run():
    """The nine readers this architecture brought are listed for its
    cell FIRST (a later cell may join them), each reads the hand-made
    run through the architecture's own counts, and a run that lacks the
    scopes and the counters reads nothing."""
    conf = _conf(CONFIG)
    arch = H.load_architecture(conf)
    run = dict(RUN, conf=conf)
    listed = {m["name"]: m for m in L.benchmark()["per_layer"]}
    for name in OWN:
        m = listed[name]
        assert m["workloads"][0] == CELL, name
        assert m["moves"] == "out_tokens_per_s"
        reader = H.load_reader(name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) \
            == (m["layer"], m["unit"], m["source"], m["moves"])
    assert {listed[n]["layer"] for n in OWN} == {
        "expert layer", "latent attention",
        "dense path beside the experts"}
    read = {name: H.load_reader(name).read(run) for name in OWN}
    assert read["zmoe_experts_share_pct"] == pytest.approx(100 * 0.9 / 4.0)
    assert read["zmoe_route_share_pct"] == pytest.approx(100 * 0.24 / 4.0)
    assert read["scmoe_dense_share_pct"] == pytest.approx(100 * 1.4 / 4.0)
    assert read["scmoe_attn_share_pct"] == pytest.approx(100 * 0.6 / 4.0)
    assert read["zmoe_zero_choice_pct"] == pytest.approx(
        100 * 2_010_000 / (12 * 500_000)) == pytest.approx(33.5)
    assert read["zmoe_tokens_per_expert"] == pytest.approx(126 / 56)
    assert read["zmoe_imbalance"] == pytest.approx(
        (24_000 / 4000) / (126 / 56))
    step_s = STEP_MS / 1e3
    cost = arch.moe_experts_cost(conf, 2, run["stats_delta"])
    assert read["zmoe_experts_roofline_pct"] == pytest.approx(
        100 * (cost[0] / 819e9) / (0.8 * step_s / CHUNK_S))
    live = 125 * (300 + 400)
    cost = arch.mla_attention_cost(conf, 2, live)
    assert read["scmoe_attn_roofline_pct"] == pytest.approx(
        100 * max(cost[0] / 819e9, cost[1] / 197e12)
        / (0.6 * step_s / CHUNK_S))
    assert all(0 < v < 100 for k, v in read.items() if k.endswith("pct"))
    # the whole step's share joins through the architecture's count
    whole = H.load_reader("decode_roofline_pct.sat").read(run)
    assert whole == pytest.approx(100 * arch.decode_step_bytes(
        conf, 2, 2, live, run["stats_delta"]) / 819e9 / step_s)
    assert 0 < whole < 100
    # a program without the scopes or the counters (the parent): nothing
    bare = dict(run, stats_delta={}, trace=dict(
        run["trace"], scopes={"while/body/dot_general": 1.0}))
    for name in OWN:
        assert H.load_reader(name).read(bare) is None, name


def test_a_cell_of_this_architecture_runs_through_run_py(tmp_path):
    """A rehearsal: the fixture's configuration and the planted tree's
    closed-loop mix, added to a copy and joined to every list the cell
    is in; one traced run through ``run.py``. The counters' readers
    read the window; what reads a device plane is left out."""
    cell = L.cell("lcflash-nano-batch", "longcat_flash-nano",
                  "lcflash-nano-batch")
    root = L.copy_with_additions(
        tmp_path,
        configs=[("longcat_flash-nano",
                  L.fixture("longcat_flash-nano.json"))],
        mixes=[("lcflash-nano-batch", L.fixture("nano-batch.json"))],
        cells=[cell], join={"lcflash-nano-batch": CELL})
    listed = {m["name"]: m.get("workloads")
              for m in L.benchmark(root)["per_layer"]}
    for name in OWN:
        assert listed[name][-1] == "lcflash-nano-batch"
    rc, out, err = L.run_copy(
        root, "--workload", "lcflash-nano-batch", "--seed",
        str(2 ** 31 + 47), "--seconds", "4", "--trace", "1",
        "--rehearsal", timeout=600)
    assert rc == 0, (out[-5:], err[-3000:])
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert 10 < got["zmoe_zero_choice_pct"] < 60
    assert got["zmoe_tokens_per_expert"] >= 1
    assert got["zmoe_imbalance"] >= 1
    assert got["compiles_in_window.sat"] == 0
    assert not {"zmoe_experts_roofline_pct", "scmoe_attn_roofline_pct",
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
