"""GPT flagship model: forward shapes, sharded train step, convergence."""
import numpy as np
import pytest


@pytest.fixture(scope="module")
def nano():
    from ray_tpu.models import gpt

    return gpt.CONFIGS["nano"]


def test_forward_shapes(nano):
    import jax

    from ray_tpu.models import gpt

    params = gpt.init_params(jax.random.PRNGKey(0), nano)
    tokens = np.zeros((2, 16), np.int32)
    logits = gpt.forward(params, tokens, nano)
    assert logits.shape == (2, 16, nano.vocab_size)
    assert logits.dtype == np.float32


def test_causality(nano):
    """Changing a future token must not affect earlier logits."""
    import jax

    from ray_tpu.models import gpt

    params = gpt.init_params(jax.random.PRNGKey(0), nano)
    t1 = np.zeros((1, 16), np.int32)
    t2 = t1.copy()
    t2[0, -1] = 7
    l1 = np.asarray(gpt.forward(params, t1, nano))
    l2 = np.asarray(gpt.forward(params, t2, nano))
    assert np.allclose(l1[0, :-1], l2[0, :-1], atol=1e-3)
    assert not np.allclose(l1[0, -1], l2[0, -1], atol=1e-3)


@pytest.mark.parametrize("axes", [{"dp": 8}, {"dp": 2, "fsdp": 2, "tp": 2},
                                  {"fsdp": 8}])
def test_sharded_train_step_loss_decreases(nano, axes):
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.parallel import create_mesh

    mesh = create_mesh(axes)
    init, step, state_sh, batch_sh = gpt.make_train_step(nano, mesh)
    state = init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jax.device_put(
        rng.integers(0, nano.vocab_size, (8, 33)).astype(np.int32),
        batch_sh)}
    losses = []
    for _ in range(5):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert int(state["step"]) == 5


@pytest.mark.parametrize("axes", [{"fsdp": 4, "tp": 2}, {"fsdp": 4}],
                         ids=["fsdp4-tp2", "fsdp4"])
def test_sharding_plans_agree(nano, axes):
    """Replicated weights (dp only) and fsdp(+tp) shardings compute the
    same loss trajectory: the gather a layer asks for by name moves
    weights, not results."""
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.parallel import create_mesh

    rng = np.random.default_rng(1)
    tokens = rng.integers(0, nano.vocab_size, (8, 33)).astype(np.int32)

    def run(axes):
        n = int(np.prod(list(axes.values())))
        mesh = create_mesh(axes, devices=jax.devices()[:n])
        init, step, _, batch_sh = gpt.make_train_step(nano, mesh)
        state = init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.device_put(tokens, batch_sh)}
        out = []
        for _ in range(3):
            state, m = step(state, batch)
            out.append(float(m["loss"]))
        return out

    a = run({"dp": 8})
    b = run(axes)
    assert np.allclose(a, b, rtol=2e-2), (a, b)


def test_graft_entry_dryrun():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_chunked_ce_matches_unchunked():
    """loss_chunk>0 reroutes the loss through _chunked_ce (the '1b'
    preset relies on it); loss AND grads must match the unchunked path,
    including a non-dividing chunk (tail) and chunk > S (fallback)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt

    cfg0 = gpt.CONFIGS["nano"]
    params = gpt.init_params(jax.random.PRNGKey(0), cfg0)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg0.vocab_size, (2, 65)),
        jnp.int32)}

    def loss_and_grad(chunk):
        cfg = dataclasses.replace(cfg0, loss_chunk=chunk)
        loss, _ = gpt.loss_fn(params, batch, cfg)
        g = jax.grad(lambda p: gpt.loss_fn(p, batch, cfg)[0])(params)
        return float(loss), g

    base_loss, base_g = loss_and_grad(0)
    for chunk in (16, 24, 1000):   # divides, tail, larger-than-S
        loss, g = loss_and_grad(chunk)
        assert abs(loss - base_loss) < 1e-4, (chunk, loss, base_loss)
        diff = max(float(jnp.abs(a - b).max())
                   for a, b in zip(jax.tree.leaves(base_g),
                                   jax.tree.leaves(g)))
        assert diff < 5e-3, (chunk, diff)


def test_kv_decode_matches_forward(nano):
    """prefill + decode_step produce the same greedy continuation as
    re-running the full forward each step (the KV cache is exact, not
    approximate)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt, gpt_decode

    params = gpt.init_params(jax.random.PRNGKey(0), nano)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, nano.vocab_size, (2, 8)).astype(np.int32)

    # Reference: greedy decode by full re-forward.
    toks = jnp.asarray(prompt)
    want = []
    for _ in range(4):
        logits = gpt.forward(params, toks, nano)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        want.append(np.asarray(nxt))
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)

    got = [np.asarray(t) for t in gpt_decode.generate(
        params, jnp.asarray(prompt), nano, max_new_tokens=4, max_len=32)]
    assert all((g == w).all() for g, w in zip(got, want)), (got, want)


def test_kv_decode_logits_close(nano):
    """Numerics: decode-step logits at each position match the full
    forward within bf16 tolerance."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt, gpt_decode

    params = gpt.init_params(jax.random.PRNGKey(1), nano)
    rng = np.random.default_rng(1)
    seq = rng.integers(0, nano.vocab_size, (1, 12)).astype(np.int32)

    full = np.asarray(gpt.forward(params, jnp.asarray(seq), nano))

    cache = gpt_decode.init_cache(nano, 1, 16)
    logits_p, cache = gpt_decode.prefill(
        params, jnp.asarray(seq[:, :8]), nano, cache)
    np.testing.assert_allclose(np.asarray(logits_p), full[:, 7],
                               rtol=0.1, atol=0.15)
    for i in range(8, 12):
        logits_d, cache = gpt_decode.decode_step(
            params, cache, jnp.asarray(seq[:, i]), nano)
        np.testing.assert_allclose(np.asarray(logits_d), full[:, i],
                                   rtol=0.1, atol=0.15)


def test_1b_config_compiles_on_8dev_fsdp_mesh():
    """The '1b' preset (VERDICT r2 weak #9): its REAL flags — chunked CE
    (loss_chunk=256), remat='dots', fsdp=8 sharding — must lower AND
    compile on the virtual 8-device mesh. AOT via ShapeDtypeStructs, so
    no 1B-param arrays materialize; GSPMD partitioning still fully
    checks the sharding plan (``benchmarks/lm_sharded.py --config 1b``
    runs this exact construction on hardware)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.parallel import create_mesh

    cfg = dataclasses.replace(gpt.CONFIGS["1b"], remat="dots",
                              attn_backend="auto")
    assert cfg.num_params() > 1_000_000_000  # it really is the 1B model
    mesh = create_mesh({"fsdp": 8})
    init, step, state_sh, batch_sh = gpt.make_train_step(cfg, mesh)

    state_shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    state_in = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state_shapes, state_sh)
    tokens = jax.ShapeDtypeStruct((16, 513), jnp.int32,
                                  sharding=batch_sh)
    lowered = step.lower(state_in, {"tokens": tokens})
    # The partitioner must actually shard the big tensors on the fsdp
    # axis — all-replicated shardings (no axis bindings) would mean the
    # 1B params are copied to every chip. Accept either lowering
    # dialect: Shardy (axis name appears in sdy.sharding bindings) or
    # GSPMD ("devices=[...]" tile assignments).
    txt = lowered.as_text()
    tiled_shardy = "sdy.sharding" in txt and '{"fsdp"' in txt
    tiled_gspmd = "devices=[" in txt
    assert tiled_shardy or tiled_gspmd, \
        "no tiled sharding annotation in lowered module"
    compiled = lowered.compile()
    assert compiled is not None
