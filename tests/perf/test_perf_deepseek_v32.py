"""The ``deepseek_v32`` architecture (DeepSeek-V3.2-Exp: A.X-K1's family
of block with a selection bias on the router and, in every layer, a
lightning indexer whose top ``index_topk`` cached tokens are all the
latent attention reads) against its plain reference
``architectures/deepseek_v32_reference.py`` at a small size on the CPU
(``fixtures/deepseek_v32-nano.json``: hidden 64, 4 heads, ranks 48 /
32, three layers of which one dense, a router 16 wide of which 8 held,
4 index heads of 32, ``index_topk`` 16): the served arithmetic on
logits at the cell's numerics where the reference can decide its own
choices; ``decidable`` following its three constants; the
configuration held to its own statement, to the catalog's widths and
to the recount of the cut; the functions that count a decode step's
bytes; the ten readers on a hand-made run; and a rehearsal of a cell
of this architecture through ``run.py`` in which most lane-steps
select. The mechanisms' controls at float32, the picked set against a
literal ``top_k``, the shares adding up to the layer and what the
model does not get are ``tests/test_serve_engine_sparse_latent.py``'s;
A.X-K1's and LongCat's lowered programs are held to the parent's by
``tests/test_models_frame.py: PARENT_TEXT``.

TOLERANCE at this size (bfloat16, the cell's numerics; my CPU run, PR
63, seeds 1-8 x 16 rows): after prefill, where nothing selects, a
decidable vector reads 0.003-0.13 (an expert an EARLIER position chose
differently reaches it through 4 heads' attention). After 24 decode
steps that pick 16 of 15-38 tokens with 4 heads, a vector whose own
selection is clear still reads up to 0.4 where earlier positions'
picks differ (at this size one token is a sixteenth of a head's
softmax and a head a quarter of the attention): the bfloat16
comparison at this size is therefore held on the rows whose WHOLE
history is decidable, which the cell's 128 heads and 2,048 picks do
not need (the configuration's ``correct.why``); the fixture itself is
float32, where every decidable vector agrees to the order of
additions and the rehearsal through ``run.py`` is ``correct``."""
import json

import numpy as np
import pytest

import perf_testlib as L

import perf_deployment
import perf_harness as H

CELL = "dsv32-ep32-reason-deep-offline"
CONFIG = "deepseek-v3.2-exp-ep32-serve"
#: the readers this architecture brought
OWN = ("dsa_index_share_pct", "dsa_select_share_pct",
       "dsa_attn_share_pct", "dsa_attn_roofline_pct", "dsa_selected_pct",
       "dsa_selecting_steps_pct", "dmoe_experts_share_pct",
       "dmoe_route_share_pct", "dmoe_experts_roofline_pct",
       "dmoe_tokens_per_expert")


def _conf(name="deepseek_v32-nano"):
    if name == "deepseek_v32-nano":
        return H.load_json(L.fixture("deepseek_v32-nano.json"))
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


def _bf16(conf):
    return dict(conf, numerics={"param_dtype": "bfloat16",
                                "compute_dtype": "bfloat16",
                                "kv_dtype": "bfloat16"},
                correct=dict(conf["correct"], logits_rel_tol=0.07))


@pytest.mark.parametrize("seed,numerics", [
    (1, "bfloat16"), (2, "bfloat16"), (3, "bfloat16"), (1, "float32")])
def test_prefill_then_selecting_steps_agree_on_logits_where_decidable(
        nano, seed, numerics):
    """System against reference on seeded weights: the paged prefill
    (13 tokens in a bucket of 16, latents and index keys into pages)
    and 25 decode steps that score, pick 16 and attend, against the
    reference's full forward pass with a literal top k at every
    position, float32 ``highest``; at the cell's numerics (bfloat16:
    the module docstring says what is compared) and at the fixture's
    (float32: every decidable vector, to the order of additions)."""
    import jax
    import jax.numpy as jnp

    conf, arch, _cfg, _ref = nano
    if numerics == "bfloat16":
        conf = _bf16(conf)
    cfg = arch.model_cfg(conf)
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
    assert dec.shape == (ck["rows"], total)
    assert 0.5 < dec[:, :n_prompt].mean() < 1       # the experts' edges
    assert dec[:, 16:].mean() < dec[:, :16].mean()  # and the selection's

    def rel(i, pos, keep):
        return np.abs(got[i][keep] - want[keep, pos]).max() \
            / np.abs(want[keep, pos]).max()

    if numerics == "float32":
        for i, pos in ((0, n_prompt - 1), (n_steps, total - 1)):
            keep = dec[:, pos]
            assert keep.any() and rel(i, pos, keep) <= 1e-4
        return
    # bfloat16 at this size: the rows whose history is decidable too
    for i, pos in ((0, n_prompt - 1), (n_steps, total - 1)):
        history = dec[:, :pos + 1].all(axis=1)
        assert i or history.any()
        assert not history.any() or rel(i, pos, history) \
            <= ck["logits_rel_tol"], (pos, rel(i, pos, history))


def test_decidable_follows_its_three_constants(nano):
    import jax.numpy as jnp

    conf, arch, cfg, ref = nano
    params = perf_deployment.seeded_params(arch, cfg, 4, conf["init"])
    weights = ref.from_program(params)
    tokens = jnp.asarray(_rows(conf, 4)[:, :-1])

    def share(**ck):
        c = dict(conf, correct=dict(conf["correct"], **ck))
        d = np.asarray(arch.decidable(cfg, c)(weights, tokens))
        return float(d[:, :16].mean()), float(d[:, 16:].mean())

    # the experts' edge alone decides a position that does not select
    free = dict(index_tie_eps=0.0, index_tie_weight=1e9)
    assert share(tie_eps=1e-9, **free) == (1.0, 1.0)
    assert share(tie_eps=10.0, **free) == (0.0, 0.0)
    a, b, c = (share(tie_eps=e, **free)[0] for e in (0.001, 0.004, 0.02))
    assert a > b > c
    # the selection's edge: no token near it, or none that carries
    # weight, and a position past index_topk is the experts' to decide
    lone = dict(tie_eps=1e-9)
    assert share(index_tie_eps=0.0, index_tie_weight=0.05, **lone)[1] == 1
    assert share(index_tie_eps=0.05, index_tie_weight=1e9, **lone)[1] == 1
    wide = share(index_tie_eps=0.05, index_tie_weight=0.05, **lone)
    near = share(index_tie_eps=0.005, index_tie_weight=0.05, **lone)
    light = share(index_tie_eps=0.05, index_tie_weight=0.5, **lone)
    assert wide[0] == near[0] == 1.0        # nothing selects before 16
    assert wide[1] < near[1] < 1.0 and wide[1] < light[1]
    # the weight is the SUM over the tokens near the edge: with every
    # cached token near it, a head's picked tokens alone carry 1.0
    assert share(index_tie_eps=1e9, index_tie_weight=1.0, **lone) \
        == (1.0, 0.0)


# ---- the cell's configuration, and what its readers count

HELD = 3_226_232_064
DENSE_LAYER, EXPERT_LAYER = 597_442_816, 599_278_080


def test_the_configuration_is_held_to_its_own_statement_and_the_catalog():
    import jax

    entry = next(c for c in L.benchmark()["configs"]
                 if c["name"] == CONFIG)
    conf = H.load_config(entry)
    arch = H.load_architecture(conf)
    L.check_configuration(entry, conf, arch)
    assert entry["source"] == ("https://huggingface.co/deepseek-ai/"
                               "DeepSeek-V3.2-Exp/blob/main/config.json")
    # every number of the catalog row's config under its own key, but
    # for the three cut; first_k_dense_replace stays as published
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "DeepSeek-V3.2-Exp")
    except OSError:
        row = None
    if row is not None:
        for key, value in row["config"].items():
            if key not in conf["reduced"]:
                assert conf[key] == value, key
    assert (conf["index_topk"], conf["index_n_heads"],
            conf["index_head_dim"], conf["router_width"]) \
        == (2048, 64, 128, 256)
    assert (conf["hidden_size"], conf["num_attention_heads"],
            conf["q_lora_rank"], conf["kv_lora_rank"],
            conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
            conf["v_head_dim"], conf["intermediate_size"],
            conf["moe_intermediate_size"], conf["num_experts_per_tok"],
            conf["n_group"], conf["topk_group"],
            conf["first_k_dense_replace"]) \
        == (7168, 128, 1536, 512, 128, 64, 128, 18432, 2048, 8, 8, 4, 3)
    cut = conf["cut"]
    assert (cut["num_hidden_layers"]["published"],
            cut["num_hidden_layers"]["held"],
            cut["num_hidden_layers"]["dense_layers_held"]) == (61, 5, 1)
    assert cut["n_routed_experts"] == {"published": 256, "held": 8}
    assert cut["vocab_size"] == {"published": 129280, "held": 16160}
    assert conf["dense_layers_held"] == 1
    assert conf["cut_stands_for"]["chips_sharing_a_layer"] == 32
    assert conf["n_routed_experts"] * 32 == 256
    # the guide's floors: four expert layers, at least 8 routed
    # experts, an eighth of the ids
    assert conf["num_hidden_layers"] - conf["dense_layers_held"] >= 4
    assert conf["n_routed_experts"] >= 8
    assert conf["vocab_size"] * 8 == 129280
    assert conf["numerics"] == {
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
        "kv_dtype": "bfloat16"}
    # the recount: the issue's arithmetic and the program's own tree,
    # to the parameter
    cfg = arch.model_cfg(conf)
    shapes = arch.param_shapes(cfg)
    count = lambda t: sum(int(np.prod(x.shape))      # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    assert count(shapes) == HELD
    assert [count(p) for p in shapes["layers"]] \
        == [DENSE_LAYER] + 4 * [EXPERT_LAYER]
    assert count(shapes["embed"]) + count(shapes["head"]) == 231_669_760
    indexer = sum(count(shapes["layers"][1][k]) for k in (
        "wiq", "wik", "wiw", "ik_norm_scale", "ik_norm_bias"))
    assert indexer == 13_959_424
    assert count(shapes["layers"][1]["router"]) == 1_835_008 + 256
    for n in (HELD, DENSE_LAYER, EXPERT_LAYER):
        assert f"{n:,}" in conf["cut_stands_for"]["how"]
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk,
            cfg.index_row, cfg.latent_row) == (64, 128, 2048, 128, 640)
    text = " ".join(conf["assumed"])
    for word in ("FP8", "Hadamard", "halves", "LayerNorm", "noaux_tc",
                 "num_nextn_predict_layers", "random"):
        assert word in text, word
    for mechanism in ("selection", "selection_bias", "index_relu",
                      "rotary", "shared_expert"):
        assert mechanism in conf["init"]["why"], mechanism
    ck = conf["correct"]
    for word in ("tie_eps", "index_tie_eps", "index_tie_weight",
                 "int8", "min_compared"):
        assert word in ck["why"], word
    assert ck["prompt_tokens"] <= 2048 < ck["prompt_tokens"] \
        + ck["decode_steps"]
    assert ck["decode_steps"] >= 1024
    assert ck["repeat_prompt"] <= 2048 < ck["repeat_prompt"] \
        + ck["repeat_answer"] // 2
    # the engine against the mix it is run under
    eng = conf["engine"]
    assert set(eng["why"]) >= {"slots", "n_pages", "prompt_buckets",
                               "prefix_cache", "attn_kernel",
                               "moe_block_rows"}
    cell = next(w for w in L.benchmark()["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reason-deep-offline", 1)
    mix = H.load_mix(cell["traffic"])
    assert mix["clients"] == 2 * eng["slots"]
    assert mix["fill_pages"] == 0 and eng["prefix_cache"] is False
    assert max(eng["prompt_buckets"]) == mix["prompt"]["max"] \
        == conf["index_topk"]
    assert mix["prompt"]["max"] + mix["answer"]["max"] == eng["max_len"]
    assert eng["slots"] * (eng["max_len"] // eng["page_size"]) \
        == eng["n_pages"]                               # no lane parks
    from ray_tpu.models import dsa_moe

    assert dsa_moe.kv_bytes_per_page(cfg, eng["page_size"]) \
        == 5 * 16 * (640 + 128) * 2 == 122_880
    assert (mix["block"], mix["pool"], mix["drain_s"]) == (16, 1024, 3)


DELTA = {"moe_steps": 150 * 4, "moe_experts_touched_sum": 600 * 8,
         "moe_tokens_here_sum": 600 * 32, "moe_expert_peak_sum": 3000,
         "dsa_tokens_scanned_sum": 150 * 128 * 2800,
         "dsa_tokens_selected_sum": 150 * 128 * 1950,
         "dsa_lane_steps_sum": 150 * 128,
         "dsa_lane_steps_selecting_sum": 150 * 100}


def test_a_decode_steps_bytes_count_the_keys_scanned_and_the_rows_picked():
    """A step multiplies by every held weight but the embedding table;
    of the routed experts by those the COUNTER says were touched; of
    the cache it needs every scanned token's index key and every PICKED
    token's latent row, from the counters, never the live tokens."""
    conf = _conf(CONFIG)
    arch = H.load_architecture(conf)
    table = conf["vocab_size"] * conf["hidden_size"]
    expert = 3 * 7168 * 2048
    cache = 5 * 2 * (128 * 2800 * 128 + 128 * 1950 * 576)
    for live in (0, 90_000):
        assert arch.decode_step_bytes(conf, 2, 2, live, DELTA) \
            == 2 * (HELD - table) + cache
    less = dict(DELTA, moe_experts_touched_sum=600 * 6.5)
    assert arch.decode_step_bytes(conf, 2, 2, 0, less) \
        == 2 * (HELD - table) + cache - 4 * 1.5 * expert * 2
    # a program without the counters: no expert and no row is assumed
    assert arch.decode_step_bytes(conf, 2, 2, 90_000, {}) \
        == 2 * (HELD - table) - 4 * 8 * expert * 2
    bytes_, flops = arch.moe_experts_cost(conf, 2, less)
    assert bytes_ == 4 * 6.5 * expert * 2
    assert flops == 4 * 32 * 2 * expert
    assert arch.moe_experts_cost(conf, 2, {}) is None
    assert arch.dsa_attention_cost(conf, 2, DELTA) == (
        cache, 5 * 2 * (128 * 2800 * 64 * 128
                        + 128 * 1950 * 128 * (576 + 512)))
    assert arch.dsa_attention_cost(conf, 2, {}) is None
    assert arch.selection_per_step(conf, DELTA) == (128 * 2800, 128 * 1950)


STEP_MS, CHUNK_S = 25.0, 2.0
RUN = {
    "conf": None, "peaks": {"hbm_bytes_per_s": 819e9,
                            "bf16_flops_per_s": 197e12,
                            "hbm_bytes": 2 ** 34},
    "stats_delta": DELTA,
    "trace_mid": 10.0,
    "rows": [{"prompt_len": 1500, "slices": [[5.0, 1], [9.0, 1299]],
              "end": None}] * 128,
    "trace": {"busy_s": 4.0, "scopes": {
        "while/body/closed_call/decode_step/dsa.attention/"
        "latent_attention/pallas_call": 0.6,
        "while/body/closed_call/decode_step/dsa.index/gather": 0.1,
        "while/body/closed_call/decode_step/dsa.index/dot_general": 0.2,
        "while/body/closed_call/decode_step/dsa.select/while/body/"
        "reduce_sum": 0.08,
        "while/body/closed_call/decode_step/dsa.select/cumsum": 0.02,
        "while/body/closed_call/decode_step/moe.experts/while/body/"
        "dot_general": 0.8,
        "moe.experts/while/body/dot_general": 0.1,
        "while/body/closed_call/decode_step/moe.route/sort": 0.2,
        "dsa.prefill/dot_general": 0.03,
        "mla.prefill/dot_general": 0.05, "other": 0.3},
        "programs": {"jit_decode_chunk_slots_paged(3)": {
            "launches": 10.0, "seconds": CHUNK_S}},
        "launches_by_host": {"engine.py:_dispatch_chunk": {
            "launches": 9, "seconds": 1.9, "programs": {
                "jit_decode_chunk_slots_paged(3)": {
                    "launches": 9, "seconds": 9 * 8 * STEP_MS / 1e3}}}}},
}


def test_the_ten_readers_on_a_hand_made_run():
    """The readers this architecture brought are listed for its cell
    alone, each reads the hand-made run through the architecture's own
    counts, and a run that lacks the scopes and the counters (the
    parent) reads nothing."""
    conf = _conf(CONFIG)
    arch = H.load_architecture(conf)
    run = dict(RUN, conf=conf)
    listed = {m["name"]: m for m in L.benchmark()["per_layer"]}
    assert tuple(m["name"] for m in L.benchmark()["per_layer"]
                 if m.get("workloads") == [CELL]) == OWN
    for name in OWN:
        m = listed[name]
        assert m["moves"] == "out_tokens_per_s"
        reader = H.load_reader(name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) \
            == (m["layer"], m["unit"], m["source"], m["moves"])
    assert {listed[n]["layer"] for n in OWN} == {
        "sparse latent attention", "expert layer"}
    read = {name: H.load_reader(name).read(run) for name in OWN}
    assert read["dsa_index_share_pct"] == pytest.approx(100 * 0.3 / 4.0)
    assert read["dsa_select_share_pct"] == pytest.approx(100 * 0.1 / 4.0)
    assert read["dsa_attn_share_pct"] == pytest.approx(100 * 0.6 / 4.0)
    assert read["dmoe_experts_share_pct"] == pytest.approx(100 * 0.9 / 4.0)
    assert read["dmoe_route_share_pct"] == pytest.approx(100 * 0.2 / 4.0)
    assert read["dsa_selected_pct"] == pytest.approx(100 * 1950 / 2800)
    assert read["dsa_selecting_steps_pct"] == pytest.approx(100 / 1.28)
    assert read["dmoe_tokens_per_expert"] == pytest.approx(4.0)
    step_s = STEP_MS / 1e3
    cost = arch.moe_experts_cost(conf, 2, DELTA)
    assert read["dmoe_experts_roofline_pct"] == pytest.approx(
        100 * (cost[0] / 819e9) / (0.8 * step_s / CHUNK_S))
    cost = arch.dsa_attention_cost(conf, 2, DELTA)
    assert read["dsa_attn_roofline_pct"] == pytest.approx(
        100 * max(cost[0] / 819e9, cost[1] / 197e12)
        / (1.0 * step_s / CHUNK_S))
    assert all(0 < v < 100 for k, v in read.items() if k.endswith("pct"))
    # the whole step's share joins through the architecture's count,
    # whatever the client's stamps say is live
    whole = H.load_reader("decode_roofline_pct.sat").read(run)
    assert whole == pytest.approx(100 * arch.decode_step_bytes(
        conf, 2, 2, 0, DELTA) / 819e9 / step_s)
    assert 0 < whole < 100
    # a program without the scopes or the counters (the parent): nothing
    bare = dict(run, stats_delta={}, trace=dict(
        run["trace"], scopes={"while/body/dot_general": 1.0}))
    for name in OWN:
        assert H.load_reader(name).read(bare) is None, name
    # the selection's small rows all under the table's ``other``: it
    # reads 0.0 (a floor) and adds nothing to the three scopes' time;
    # without the indexer's or the attention's scope there is no reading
    two = dict(run, trace=dict(run["trace"], scopes={
        k: v for k, v in run["trace"]["scopes"].items()
        if "dsa.select" not in k}))
    assert H.load_reader("dsa_select_share_pct").read(two) == 0.0
    assert H.load_reader("dsa_attn_roofline_pct").read(two) \
        == pytest.approx(100 * max(cost[0] / 819e9, cost[1] / 197e12)
                         / (0.9 * step_s / CHUNK_S))
    for gone in ("dsa.index", "dsa.attention"):
        less = dict(run, trace=dict(run["trace"], scopes={
            k: v for k, v in run["trace"]["scopes"].items()
            if gone not in k}))
        assert H.load_reader("dsa_attn_roofline_pct").read(less) is None


def test_a_cell_of_this_architecture_selects_through_run_py(tmp_path):
    """A rehearsal: the fixture's configuration and a mix whose prompts
    stay under ``index_topk`` and whose answers run past it, added to a
    copy and joined to every list the cell is in; one traced run
    through ``run.py``. The counters' readers read the window; what
    reads a device plane is left out. The fixture KEEPS a prefix cache
    (the cell has none), so the check request's hit and its copy-on-
    write fork carry index keys here."""
    cell = L.cell("dsv32-nano-batch", "deepseek_v32-nano",
                  "dsv32-nano-batch")
    root = L.copy_with_additions(
        tmp_path,
        configs=[("deepseek_v32-nano",
                  L.fixture("deepseek_v32-nano.json"))],
        mixes=[("dsv32-nano-batch", L.fixture("dsv32-nano-batch.json"))],
        cells=[cell], join={"dsv32-nano-batch": CELL})
    listed = {m["name"]: m.get("workloads")
              for m in L.benchmark(root)["per_layer"]}
    for name in OWN:
        assert listed[name] == [CELL, "dsv32-nano-batch"]
    rc, out, err = L.run_copy(
        root, "--workload", "dsv32-nano-batch", "--seed",
        str(2 ** 31 + 63), "--seconds", "4", "--trace", "1",
        "--rehearsal", timeout=600)
    assert rc == 0, (out[-5:], err[-3000:])
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert 50 < got["dsa_selecting_steps_pct"] <= 100
    assert 30 < got["dsa_selected_pct"] < 100
    assert got["dmoe_tokens_per_expert"] >= 1
    assert got["compiles_in_window.sat"] == 0
    assert not {"dsa_attn_roofline_pct", "dmoe_experts_roofline_pct",
                "decode_roofline_pct.sat"} & set(got)
    setup = json.loads(next(ln for ln in out
                            if ln.startswith("SETUP "))[6:])
    served = setup["served_check"]
    assert served["complete"] and served["hit_after_eviction"]
    assert served["reference"]["control_max_gap"] \
        > served["reference"]["control_margin"]
    assert setup["reference_vectors"]["compared"] >= 1
