"""The plain reference against the program's model at ``nano``: it
shares no code with ``ray_tpu/models`` and agrees with it."""
import dataclasses
import hashlib
import json
import os

import numpy as np
import pytest

import perf_testlib

import kernel_costs
import perf_harness
import reference_gpt2


@pytest.fixture(scope="module")
def nano():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg = dataclasses.replace(gpt.CONFIGS["nano"], dtype=jnp.float32,
                              remat="none")
    params = gpt.init_params(jax.random.PRNGKey(3), cfg)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 33)).astype(np.int32)
    return gpt, cfg, params, tokens


def test_reference_shares_no_code_with_the_program():
    with open(os.path.join(perf_testlib.PERF, "reference_gpt2.py")) as f:
        src = f.read()
    assert "import ray_tpu" not in src and "from ray_tpu" not in src
    # it stays where it is, byte for byte: a later PR adds its own
    # reference beside its architecture file and edits none
    assert hashlib.sha256(src.encode()).hexdigest() == \
        "b011ef468e267f2be780de2a2c4e1803945ee68f00acc0eb1a090409f7f551ec"


@pytest.fixture(scope="module", params=["tree", "rehearsal"])
def root(request, tmp_path_factory):
    return perf_testlib.root_of(request.param, tmp_path_factory)


def test_only_the_architecture_file_knows_the_model(root):
    """The harness finds a model by name: the files under
    ``benchmarks/perf`` that import the program's model or engine code,
    or name a reference module, all lie under ``architectures/``, each
    ``architectures/<name>.py`` names no reference but its own, and no
    reference imports the program (``perf_testlib.
    who_knows_the_model``). A RULE for any number of architectures (PR
    36; it was a list of the one file, which a second one tripped):
    every architecture file knows the model and nothing else does;
    beside ``reference_gpt2.py`` (pinned above) there is exactly one
    ``architectures/<name>_reference.py`` for every
    ``architectures/<name>.py`` but gpt2's, and no reference without
    its module. Held on the tree and on the rehearsal's copy, which is
    the tree's set plus ``dummy``, whose module imports
    ``ray_tpu.models`` openly."""
    held = perf_testlib.architectures_and_references(
        perf_testlib.perf_dir(root))
    assert "gpt2" in held
    if root != perf_testlib.ROOT:
        assert held == sorted(perf_testlib.architectures_and_references(
            perf_testlib.PERF) + ["dummy"])


@pytest.mark.parametrize("fault", [
    "a_harness_file_imports_the_model",
    "a_reader_imports_the_engine_through_importlib",
    "an_architecture_names_anothers_reference",
    "a_reference_imports_the_program"])
def test_a_planted_file_that_knows_the_model_is_refused(tmp_path, fault):
    root = perf_testlib.rehearsal_copy(tmp_path)
    perf = perf_testlib.perf_dir(root)
    perf_testlib.who_knows_the_model(perf)              # sound as it is
    where, text = {
        "a_harness_file_imports_the_model": (
            "perf_extra.py",
            "def f():\n    from ray_tpu.models import gpt\n"
            "    return gpt\n"),
        "a_reader_imports_the_engine_through_importlib": (
            os.path.join("layer_metrics", "sly.py"),
            "import importlib\n\n\ndef read(run):\n    return "
            "importlib.import_module('ray_tpu.serve.engine')\n"),
        "an_architecture_names_anothers_reference": (
            os.path.join("architectures", "third.py"),
            "def reference(cfg):\n    import reference_gpt2\n"
            "    return reference_gpt2\n"),
        "a_reference_imports_the_program": (
            os.path.join("architectures", "third_reference.py"),
            "from ray_tpu.models import gpt\n"),
    }[fault]
    with open(os.path.join(perf, where), "w") as f:
        f.write(text)
    with pytest.raises(AssertionError, match=os.path.basename(where)):
        perf_testlib.who_knows_the_model(perf)


def test_forward_agrees_with_the_program_in_float32(nano):
    import jax

    gpt, cfg, params, tokens = nano
    with jax.default_matmul_precision("highest"):
        want = np.asarray(gpt.forward(params, tokens[:, :-1], cfg))
    got = np.asarray(reference_gpt2.forward(
        reference_gpt2.from_program(params), tokens[:, :-1], cfg.n_head))
    # both float32: only the order of additions differs
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def _int8(x):
    """Weights as int8 with one scale per output channel, back in
    float32: what an int8 weight path would multiply with."""
    x = np.asarray(x, np.float32)
    if x.ndim < 2:
        return x
    s = np.abs(x).max(axis=-2, keepdims=True) / 127.0
    return (np.round(x / np.where(s == 0, 1, s)) * s).astype(np.float32)


def _fp8(x):
    """Weights rounded to float8 e4m3 (4 significand bits)."""
    import jax.numpy as jnp

    x = np.asarray(x, np.float32)
    if x.ndim < 2:
        return x
    return np.asarray(jnp.asarray(x).astype(jnp.float8_e4m3fn)
                      .astype(jnp.float32))


def _serve_conf():
    return perf_harness.load_json(os.path.join(
        perf_testlib.PERF, "configs", "cerebras-gpt-1.3b-serve.json"))


def _serve_tol():
    return _serve_conf()["correct"]["logits_rel_tol"]


def test_bfloat16_program_is_inside_the_logits_tolerance(nano):
    import jax.numpy as jnp

    gpt, cfg, params, tokens = nano
    ref = np.asarray(reference_gpt2.forward(
        reference_gpt2.from_program(params), tokens[:, :-1], cfg.n_head))
    bf = np.asarray(gpt.forward(
        params, tokens[:, :-1],
        dataclasses.replace(cfg, dtype=jnp.bfloat16)))
    assert np.abs(bf - ref).max() / np.abs(ref).max() <= _serve_tol()


@pytest.mark.parametrize("cruder", [_int8, _fp8], ids=["int8", "fp8"])
def test_cruder_weights_are_outside_the_logits_tolerance(nano, cruder):
    """The tolerance the serving configuration states refuses int8 and
    fp8 weights even when everything else is float32."""
    import jax

    gpt, cfg, params, tokens = nano
    ref = np.asarray(reference_gpt2.forward(
        reference_gpt2.from_program(params), tokens[:, :-1], cfg.n_head))
    bad = np.asarray(gpt.forward(jax.tree_util.tree_map(cruder, params),
                                 tokens[:, :-1], cfg))
    assert np.abs(bad - ref).max() / np.abs(ref).max() > _serve_tol()


def test_served_tokens_are_ranked_by_the_reference(nano):
    """``token_gaps``: 0 where the served token is the reference's best,
    its distance below the best elsewhere; tokens of another sequence
    (what a wrong page yields) lie far outside the margin."""
    import perf_reference_check as C

    gpt, cfg, params, tokens = nano
    ref = np.asarray(reference_gpt2.forward(
        reference_gpt2.from_program(params), tokens[:, :-1], cfg.n_head))
    best = ref[0].argmax(-1)
    assert (C.token_gaps(ref[0], best) == 0).all()
    second = np.argsort(ref[0], -1)[:, -2]
    gaps = C.token_gaps(ref[0], second)
    assert (gaps > 0).all() and gaps == pytest.approx(
        np.sort(ref[0], -1)[:, -1] - np.sort(ref[0], -1)[:, -2])
    margin = 2 * _serve_tol() * np.abs(ref[0]).max()
    other = ref[1].argmax(-1)            # best tokens of another prompt
    assert C.token_gaps(ref[0], other).max() > 3 * margin
    # the verdict: the program's own greedy tokens pass, another
    # sequence's fail
    prompt, n = tokens[0, :20], 8
    seq = [int(t) for t in prompt]
    for _ in range(n):
        with __import__("jax").default_matmul_precision("highest"):
            lg = np.asarray(gpt.forward(params, np.asarray([seq]), cfg))
        seq.append(int(lg[0, -1].argmax()))
    rows = np.asarray(reference_gpt2.forward(
        reference_gpt2.from_program(params), np.asarray([seq[:-1]]),
        cfg.n_head))[0, 19:]
    good = C.served_verdict(rows, seq[20:], _serve_tol())
    assert good["ok"] and good["agree"] == n and good["tokens"] == n
    bad = C.served_verdict(rows, tokens[1, :n], _serve_tol())
    assert not bad["ok"] and bad["max_gap"] > 3 * bad["margin"]


def test_loss_agrees_with_the_program(nano):
    gpt, cfg, params, tokens = nano
    want = float(gpt.loss_fn(params, {"tokens": tokens}, cfg)[0])
    got = float(reference_gpt2.loss(reference_gpt2.from_program(params),
                                    tokens, cfg.n_head))
    assert got == pytest.approx(want, abs=1e-4)


def test_parameter_count_is_the_programs_and_costs_follow_shapes(nano):
    gpt, cfg, _params, _tokens = nano
    model = _serve_conf()["model"]
    assert kernel_costs.n_params(model) == gpt.CONFIGS["1b"].num_params()
    assert 1.2e9 < kernel_costs.n_params(model) < 1.45e9
    # a decode step multiplies by everything but the position table, and
    # is charged each such weight once in the dtype it is multiplied in
    assert kernel_costs.decode_weight_params(model) == \
        kernel_costs.n_params(model) - 2048 * 2048
    b0 = kernel_costs.decode_step_bytes(model, 2, 2, 0)
    b1 = kernel_costs.decode_step_bytes(model, 2, 2, 1000)
    assert b0 == 2 * kernel_costs.decode_weight_params(model)
    assert 2.60e9 < b0 < 2.65e9
    assert b1 - b0 == 2 * 24 * 2048 * 2 * 1000
    assert kernel_costs.train_flops_per_token(model, 2048) == \
        6 * kernel_costs.n_params(model) + 12 * 24 * 2048 * 2048


def _decode_run(step_ms, live, numerics=None):
    """What ``decode_roofline_pct`` reads of a traced serving run, made
    by hand at the 1.3B configuration: eleven chunk launches of
    ``step_ms`` a token step while the driver waited for a chunk, and
    one stream whose prompt and delivered tokens sum to ``live`` at the
    slice's middle, beside one that ended before it and one that
    started after."""
    from program_names import CHUNK_WAIT

    conf = _serve_conf()
    if numerics is not None:
        conf["numerics"] = numerics
    launches = 11
    seconds = step_ms * 1e-3 * conf["engine"]["chunk"] * launches
    return {
        "conf": conf, "trace_mid": 10.0,
        "peaks": perf_harness.peaks("TPU v5 lite"),
        "trace": {"launches_by_host": {CHUNK_WAIT: {
            "launches": launches, "seconds": seconds,
            "programs": {"jit_decode_chunk_slots_paged": {
                "launches": launches, "seconds": seconds}}}}},
        "rows": [
            {"prompt_len": live - 24, "end": None,
             "slices": [[8.0, 16], [9.5, 8], [10.5, 8]]},
            {"prompt_len": 300, "end": 9.0, "slices": [[7.0, 64]]},
            {"prompt_len": 300, "end": None, "slices": [[10.2, 8]]}]}


@pytest.mark.parametrize("step_ms,live,want", [
    (36.666, 966, 9.4), (119.51, 13102, 5.3),     # the tree (ledger, PR 31)
    (5.7223, 466, 57.9), (9.1292, 11969, 66.5)],  # PR 32's change (ledger)
    ids=["tree-chat", "tree-batch", "pr32-chat", "pr32-batch"])
def test_decode_roofline_reads_the_ledgers_steps(step_ms, live, want):
    """The ledger's four steps under the count of PR 33 (weights once in
    the compute dtype, 2.62 GB = 3.2 ms, and the live keys and values):
    the two that read 114.2 and 101.8 under 4 bytes a weight are a sound
    58 and 67."""
    run = _decode_run(step_ms, live)
    got = perf_harness.load_reader("decode_roofline_pct").read(run)
    assert got == pytest.approx(want, abs=0.3)
    assert got == pytest.approx(100 * kernel_costs.decode_step_bytes(
        run["conf"]["model"], 2, 2, live) / 819e9 / (step_ms / 1e3))


@pytest.mark.parametrize("held", ["float32", "bfloat16"])
def test_decode_roofline_ignores_how_the_weights_are_held(held):
    """Two configurations that differ only in ``param_dtype`` read the
    same: the bytes are any program's, not this one's."""
    base = _decode_run(36.666, 966)
    other = _decode_run(36.666, 966, numerics={
        "param_dtype": held, "compute_dtype": "bfloat16",
        "kv_dtype": "bfloat16"})
    read = perf_harness.load_reader("decode_roofline_pct").read
    assert read(other) == read(base)


@pytest.mark.parametrize("case", ["a_step_no_chip_can_make",
                                  "no_compute_dtype", "the_sat_twin"])
def test_decode_roofline_still_bites(case):
    read = perf_harness.load_reader("decode_roofline_pct").read
    if case == "a_step_no_chip_can_make":
        # 2.5 ms at PR 32's live tokens: layers left out, or time lost.
        # The driver refuses a share any run reads above 105
        got = read(_decode_run(2.5, 466))
        assert got > 105 and got == pytest.approx(132.5, abs=0.5)
    elif case == "no_compute_dtype":
        run = _decode_run(36.666, 966, numerics={
            "param_dtype": "float32", "kv_dtype": "bfloat16"})
        with pytest.raises(KeyError, match="compute_dtype"):
            read(run)
    else:
        run = _decode_run(9.1292, 11969)
        sat = perf_harness.load_reader("decode_roofline_pct.sat")
        assert sat.read(run) == read(run)
        assert (sat.LAYER, sat.UNIT) == ("kernels", "%")


STUB = '''"""A stub architecture: a dense part, and eight routed experts of
which a step reads those a live token was routed to, by the engine's
counter."""


def decode_step_bytes(conf, weight_bytes, kv_bytes, live_tokens,
                      stats_delta):
    m = conf["model"]
    touched = stats_delta.get("experts_touched_mean", m["experts"])
    return (m["dense"] + touched * m["expert"]) * weight_bytes \\
        + m["latent"] * kv_bytes * live_tokens
'''


@pytest.mark.parametrize("case", [
    "bytes_from_the_stub", "routed_experts_from_the_counter",
    "a_module_without_the_function", "no_such_architecture_file"])
def test_decode_roofline_asks_the_configurations_architecture(tmp_path,
                                                              case):
    """The reader's path since PR 36, on a hand-made run: the numerator
    is ``decode_step_bytes`` of ``architectures/<conf["architecture"]>
    .py`` beside the reader, given the configuration, the two byte
    widths, the live tokens and the window's counters; a module without
    the function has no such count and the reader returns nothing."""
    import shutil

    here = str(tmp_path)
    for sub in ("layer_metrics", "architectures"):
        os.mkdir(os.path.join(here, sub))
    shutil.copy(os.path.join(perf_testlib.PERF, "layer_metrics",
                             "decode_roofline_pct.py"),
                os.path.join(here, "layer_metrics"))
    for name, text in (("stub", STUB), ("bare", "def vocab(conf):\n"
                                                "    return 1, 1\n")):
        with open(os.path.join(here, "architectures", name + ".py"),
                  "w") as f:
            f.write(text)
    run = _decode_run(10.0, 1024)
    model = {"dense": 10 ** 9, "expert": 10 ** 8, "experts": 8,
             "latent": 576}
    run["conf"] = dict(run["conf"], architecture="stub", model=model)
    read = perf_harness.load_reader("decode_roofline_pct", here).read

    def share(nbytes):
        return pytest.approx(100 * nbytes / 819e9 / 10e-3)

    kv = 576 * 2 * 1024
    if case == "bytes_from_the_stub":
        # no counter in a run without stats_delta: every expert
        assert read(run) == share(2 * (10 ** 9 + 8 * 10 ** 8) + kv)
    elif case == "routed_experts_from_the_counter":
        run["stats_delta"] = {"experts_touched_mean": 5.5}
        assert read(run) == share(2 * (10 ** 9 + 5.5 * 10 ** 8) + kv)
        run["conf"]["numerics"] = dict(run["conf"]["numerics"],
                                       compute_dtype="float32")
        run["conf"]["engine"] = dict(run["conf"]["engine"],
                                     kv_dtype="int8")
        assert read(run) == share(4 * (10 ** 9 + 5.5 * 10 ** 8)
                                  + kv // 2)
    elif case == "a_module_without_the_function":
        run["conf"]["architecture"] = "bare"
        assert read(run) is None
    else:
        run["conf"]["architecture"] = "absent"
        with pytest.raises(perf_harness.BenchError,
                           match="no architecture file"):
            read(run)


def _synthetic(rng, n=9, rows=300, best=4.0):
    """Reference logits [n, rows] of standard deviation ~0.9 whose best
    token at every position is known, with the greedy answer."""
    ref = rng.normal(0.0, 0.9, (n, rows)).astype(np.float32).clip(-3, 3)
    answer = rng.integers(0, rows, n)
    ref[np.arange(n), answer] = best
    return ref, answer


@pytest.mark.parametrize("case,ok", [
    ("one_answer", True),
    ("parted_at_a_near_tie", True),
    ("parted_where_the_reference_is_sure", False),
    ("an_answer_from_another_prompt", False),
    ("a_control_that_passes", False)])
def test_every_distinct_answer_meets_the_reference(case, ok):
    """``served_verdicts`` on planted reference logits: answers may
    part where the reference cannot tell their tokens apart and nowhere
    else; each is judged along its own tokens; and a control that
    passes (the check has gone blind) makes the run not correct."""
    import perf_reference_check as C

    tol = _serve_tol()
    rng = np.random.default_rng(27)
    ref, first = _synthetic(rng)
    other_ref, other = _synthetic(rng)
    margin = 2 * tol * 4.0
    refs, answers, control = [ref], [first], other_ref
    if case.startswith("parted"):
        gap = 0.1 * margin if case == "parted_at_a_near_tie" \
            else 4 * margin
        second = first.copy()
        second[6] = (first[6] + 1) % ref.shape[1]
        ref[6, second[6]] = 4.0 - gap
        # the second answer's own row: the same up to where it parts,
        # then logits of its own, of which it picks the best
        ref2, tail = _synthetic(rng)
        ref2[:7] = ref[:7]
        second[7:] = tail[7:]
        refs, answers = [ref, ref2], [first, second]
    elif case == "an_answer_from_another_prompt":
        refs, answers = [ref, ref], [first, other]
    elif case == "a_control_that_passes":
        control = ref
    got = C.served_verdicts(refs, answers, tol, control)
    assert got["ok"] is ok, got
    assert got["distinct"] == len(answers)
    assert got["margin"] == pytest.approx(margin)
    if case.startswith("parted"):
        assert got["parted"]["at"] == 6
        assert got["parted"]["ref_gap"] == pytest.approx(gap, rel=1e-3)
        assert got["max_gap"] == pytest.approx(gap, rel=1e-3)
        assert [v["ok"] for v in got["each"]] == [True, ok]
    if case == "an_answer_from_another_prompt":
        assert got["max_gap"] > 3 * margin and got["each"][0]["ok"]
    if case == "a_control_that_passes":
        assert got["control_max_gap"] <= got["control_margin"]
        assert got["max_gap"] == 0.0          # the answer itself is sound
    else:
        assert got["control_max_gap"] > 3 * got["control_margin"]


def test_a_planted_page_fault_reads_not_correct_through_the_engine(
        tmp_path):
    """At ``nano`` through the handle and the engine on the CPU: an
    engine whose prefix-cache hits read their pages in the wrong order
    (``fixtures/faulty_pages.py``, added to a copy as an architecture)
    answers the check request's resends from other positions' keys. The
    answers differ, which alone no longer fails a run; the reference
    ranks the wrong one far outside the margin, and that does."""
    with open(os.path.join(perf_testlib.FIXTURES, "nano-serve.json")) as f:
        conf = json.load(f)
    conf["architecture"] = "faulty_pages"
    conf_path = os.path.join(str(tmp_path), "nano-faulty.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)
    root = perf_testlib.copy_with_additions(
        tmp_path, configs=[("nano-faulty", conf_path)],
        mixes=[("nano-chat", os.path.join(perf_testlib.FIXTURES,
                                          "nano-chat.json"))],
        architectures=[("faulty_pages.py", os.path.join(
            perf_testlib.FIXTURES, "faulty_pages.py"))],
        cells=[{"name": "nano-faulty", "config": "nano-faulty",
                "traffic": "nano-chat", "chips": 1, "why": "test"}],
        join={"nano-faulty": "cgpt1b3-chat-steady"})
    rc, out, err = perf_testlib.run_copy(
        root, "--workload", "nano-faulty", "--seed", str(2 ** 31 + 7),
        "--seconds", "2", "--trace", "0", "--rehearsal")
    assert rc == 0, (out[-5:], err[-2000:])
    res = json.loads(out[-1])
    assert res["correct"] is False and res["failed"] == 0
    check = json.loads(next(ln for ln in out
                            if ln.startswith("SERVED-CHECK "))[13:])
    ref = check["reference"]
    assert check["complete"] and not check["identical"]
    assert ref["distinct"] == 2 and not ref["ok"]
    assert ref["each"][0]["ok"] and not ref["each"][1]["ok"]
    assert ref["max_gap"] > 3 * ref["margin"]
    # the arithmetic itself is sound: only the tokens give the fault away
    setup = json.loads(next(ln for ln in out
                            if ln.startswith("SETUP "))[6:])
    assert all(c["rel"] <= c["tol"] for c in setup["reference"])


# ---- what the reference cannot decide is left out, counted and bounded

N_PROMPT, N_STEPS = 10, 4
TOY_SEED = 2 ** 31 + 29


@pytest.fixture(scope="module")
def toy():
    import perf_harness as H

    return H.load_file(os.path.join(perf_testlib.FIXTURES,
                                    "toy_router_arch.py"), "perf_arch_")


def _toy_conf(**correct):
    return {"correct": dict({
        "logits_rel_tol": 0.025, "tie_eps": 1e-3,
        "why": "tie_eps 1e-3: bfloat16 moves a score by up to 3e-2"},
        **correct)}


def _seeded_rows(seed, rows, vocab):
    """``serve_check``'s own draw, as it has been since PR 23."""
    rng = np.random.default_rng([seed & (2 ** 63 - 1), 77])
    return rng.integers(0, vocab, (rows, N_PROMPT + N_STEPS + 1)
                        ).astype(np.int32)


def _without_decidable(toy):
    import types

    return types.SimpleNamespace(**{
        k: getattr(toy, k) for k in ("vocab", "served_logits",
                                     "reference")})


def _check(arch, engine, conf, served=None):
    import perf_reference_check as C

    return C.serve_check(arch, engine, None, conf, TOY_SEED, N_PROMPT,
                         N_STEPS, served)


def test_rows_2_and_no_decidable_compares_what_the_parent_compared(toy):
    """The numbers of a module without ``decidable`` are PR 27's to the
    last digit: its formula, written out here, on the same draw."""
    import jax
    import jax.numpy as jnp

    engine = toy.Engine(toy.init_params(5))
    got = _check(_without_decidable(toy), engine, _toy_conf())
    seqs = _seeded_rows(TOY_SEED, 2, toy.V)
    served = toy.served_logits(engine, None, seqs, N_PROMPT, N_STEPS)
    ref = np.asarray(jax.jit(toy.reference(None)[1])(
        engine.params, jnp.asarray(seqs[:, :-1])), np.float32)
    for c, (i, pos) in zip(got["checks"], (
            (0, N_PROMPT - 1), (N_STEPS, N_PROMPT + N_STEPS - 1))):
        err = float(np.abs(served[i] - ref[:, pos]).max())
        scale = float(np.abs(ref[:, pos]).max())
        assert (c["max_abs_err"], c["max_abs_ref"], c["rel"]) == \
            (err, scale, err / scale)
        assert (c["compared"], c["left_out"]) == (2, 0)
        assert c["rel"] <= c["tol"]
    assert got["ok"] and got["vectors"] == {
        "compared": 4, "left_out": 0, "needed": 4}
    # with decidable and no near-tie among the four: the same numbers
    with_dec = _check(toy, engine, _toy_conf())
    assert with_dec["checks"] == got["checks"] and with_dec["ok"]


def test_a_planted_near_tie_is_left_out_and_counted(toy):
    """Row 1's last compared position is made a near-tie of the two
    experts: program and reference pick differently there, the logits
    part by far more than the tolerance, and the harness leaves that
    one vector out, says so, and needs ``min_compared`` to allow it."""
    seqs = _seeded_rows(TOY_SEED, 2, toy.V)[:, :-1]
    pos = N_PROMPT + N_STEPS - 1
    params = toy.plant_near_tie(toy.init_params(5), seqs, 1, pos)
    ref_s = toy.scores(params, seqs, program=False)
    prog_s = toy.scores(params, seqs, program=True)
    assert abs(ref_s[1, pos, 0] - ref_s[1, pos, 1]) < 1e-4
    assert ref_s[1, pos].argmax() != prog_s[1, pos].argmax()
    engine = toy.Engine(params)
    blind = _check(_without_decidable(toy), engine, _toy_conf())
    assert not blind["ok"]
    assert blind["checks"][1]["rel"] > 2 * blind["checks"][1]["tol"]
    got = _check(toy, engine, _toy_conf(min_compared=0.5))
    assert got["ok"], got
    assert [(c["compared"], c["left_out"]) for c in got["checks"]] == \
        [(2, 0), (1, 1)]
    assert got["checks"][1]["rel"] <= got["checks"][1]["tol"]
    assert got["vectors"] == {"compared": 3, "left_out": 1, "needed": 2}
    # by default every vector has to be compared: too few is not correct
    strict = _check(toy, engine, _toy_conf())
    assert not strict["ok"] and strict["vectors"]["needed"] == 4
    assert strict["checks"] == got["checks"]


def test_a_decidable_that_says_no_everywhere_is_not_correct(toy):
    engine = toy.Engine(toy.init_params(5))
    prompt = _seeded_rows(TOY_SEED + 1, 1, toy.V)[0, :N_PROMPT]
    answer = toy.greedy(engine, prompt, 6)
    got = _check(toy, engine, _toy_conf(tie_eps=1e9, min_compared=0.01),
                 (prompt, [answer]))
    assert not got["ok"] and not got["served"]["ok"]
    assert [c["compared"] for c in got["checks"]] == [0, 0]
    assert all(c["rel"] is None for c in got["checks"])
    assert got["served"]["compared"] == 0
    assert got["served"]["left_out"] == 6


def test_a_faulty_expert_at_a_decidable_position_is_not_correct(toy):
    """The program multiplies with expert weights that are off by half;
    what is left out does not change, since no call into the program
    decides it, and what is compared fails by far."""
    import copy

    params = toy.init_params(5)
    bad = copy.deepcopy(params)
    bad["down"] = bad["down"] * 1.5
    sound = _check(toy, toy.Engine(params), _toy_conf())
    got = _check(toy, toy.Engine(params, program_params=bad),
                 _toy_conf())
    assert sound["ok"] and not got["ok"]
    assert [(c["compared"], c["left_out"]) for c in got["checks"]] == \
        [(c["compared"], c["left_out"]) for c in sound["checks"]]
    assert max(c["rel"] for c in got["checks"]) > 2 * 0.025


def test_a_served_token_at_a_near_tie_is_left_out_of_its_verdict(toy):
    """The answer's first token is chosen where the reference calls a
    near-tie: the program took the other expert's logits, the reference
    ranks its token far below its best, and the verdict leaves that one
    token out, counts it, and still fails the control."""
    prompt = _seeded_rows(TOY_SEED + 1, 1, toy.V)[:, :N_PROMPT]
    params = None
    for seed in range(5, 40):       # a seed whose parted token differs
        params = toy.plant_near_tie(toy.init_params(seed), prompt, 0,
                                    N_PROMPT - 1)
        engine = toy.Engine(params)
        answer = toy.greedy(engine, prompt[0], 6)
        blind = _check(_without_decidable(toy), engine, _toy_conf(),
                       (prompt[0], [answer]))["served"]
        if blind["max_gap"] > blind["margin"]:
            break
    assert not blind["ok"] and blind["left_out"] == 0
    got = _check(toy, engine, _toy_conf(min_compared=0.5),
                 (prompt[0], [answer]))["served"]
    assert got["ok"], got
    assert (got["tokens"], got["compared"], got["left_out"]) == (6, 5, 1)
    assert got["max_gap"] <= got["margin"] < blind["max_gap"]
    assert got["control_max_gap"] > got["control_margin"]
    assert got["min_compared"] == 0.5
    # all of an answer's tokens by default: one left out is too few
    strict = _check(toy, engine, _toy_conf(), (prompt[0], [answer]))
    assert not strict["served"]["ok"]
