"""Mesh, sharding-rule, and collective-group tests on the virtual 8-CPU mesh.

Mirrors the reference's collective test layout
(``python/ray/util/collective/tests/single_node_cpu_tests/``) with the xla
mesh backend in place of gloo.
"""
import numpy as np
import pytest


@pytest.fixture(scope="module")
def mesh8():
    from ray_tpu.parallel import create_mesh

    return create_mesh({"dp": 8})


def test_mesh_axes_resolution():
    from ray_tpu.parallel import create_mesh, mesh_shape

    m = create_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    assert mesh_shape(m) == {"dp": 2, "fsdp": 2, "tp": 2}
    # tp must be the innermost (last) axis
    assert m.axis_names[-1] == "tp"

    m2 = create_mesh({"dp": -1, "tp": 2})
    assert mesh_shape(m2) == {"dp": 4, "tp": 2}


def test_mesh_bad_shape():
    from ray_tpu.parallel import create_mesh

    with pytest.raises(ValueError):
        create_mesh({"dp": 3, "tp": 3})


def test_sharding_rules(mesh8):
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel import spec_for, LM_RULES

    assert spec_for("block/wq/kernel", (64, 64), LM_RULES, mesh8) != None  # noqa
    # dp-only mesh: fsdp/tp axes degrade to replication
    s = spec_for("block/wq/kernel", (64, 64), LM_RULES, mesh8)
    assert s == P()


def test_sharding_rules_fsdp_tp():
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel import create_mesh, spec_for, LM_RULES

    m = create_mesh({"fsdp": 4, "tp": 2})
    assert spec_for("block/wq/kernel", (64, 64), LM_RULES, m) == \
        P(("fsdp",), "tp")
    # indivisible dim → that dim replicated
    assert spec_for("block/wq/kernel", (63, 64), LM_RULES, m) == \
        P(None, "tp")
    assert spec_for("ln1_scale", (64,), LM_RULES, m) == P()


# The shapes the model really has (models/gpt.py init_params at L=6,
# d=64, f=256, E=4): a block's matrices are STACKED, and a stacked
# matrix is never sharded on its layer dimension by fsdp or tp.
_L, _D, _F, _E = 6, 64, 256, 4
STACKED_SPECS = {
    # leaf: (shape, spec on {"fsdp": 4, "tp": 2}, spec on {"fsdp": 4})
    "block/wq/kernel": ((_L, _D, _D), (None, "fsdp", "tp"), (None, "fsdp")),
    "block/wk/kernel": ((_L, _D, _D), (None, "fsdp", "tp"), (None, "fsdp")),
    "block/wv/kernel": ((_L, _D, _D), (None, "fsdp", "tp"), (None, "fsdp")),
    "block/wo/kernel": ((_L, _D, _D), (None, "tp", "fsdp"),
                        (None, None, "fsdp")),
    "block/w1/kernel": ((_L, _D, _F), (None, "fsdp", "tp"), (None, "fsdp")),
    "block/w2/kernel": ((_L, _F, _D), (None, "tp", "fsdp"),
                        (None, None, "fsdp")),
    # Adam's moments carry the parameter's path as a suffix
    "opt/0/mu/block/w1/kernel": ((_L, _D, _F), (None, "fsdp", "tp"),
                                 (None, "fsdp")),
    "block/ln1_scale": ((_L, _D), (), ()),
    "embed/kernel": ((512, _D), ("fsdp", "tp"), ("fsdp",)),
    "pos_embed": ((128, _D), (None, "fsdp"), (None, "fsdp")),
    # the expert rules name L and shard it on purpose (ROADMAP T1)
    "block/router/kernel": ((8, _D, _E), ("fsdp",), ("fsdp",)),
    "block/w_up/kernel": ((8, _E, _D, _F), ("fsdp", None, None, "tp"),
                          ("fsdp",)),
}


@pytest.mark.parametrize("leaf", sorted(STACKED_SPECS))
def test_sharding_rules_stacked_shapes(leaf):
    import jax
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel import create_mesh, spec_for, LM_RULES
    from ray_tpu.parallel.sharding import PP_LM_RULES

    shape, both, fsdp = STACKED_SPECS[leaf]
    m = create_mesh({"fsdp": 4, "tp": 2})
    assert spec_for(leaf, shape, LM_RULES, m) == P(*both)
    m4 = create_mesh({"fsdp": 4}, devices=jax.devices()[:4])
    assert spec_for(leaf, shape, LM_RULES, m4) == P(*fsdp)
    # an unstacked matrix under the same rule: its own two dimensions
    if len(shape) == 3 and "router" not in leaf:
        assert spec_for(leaf, shape[1:], LM_RULES, m) == P(*both[1:])
    # the pipeline's rules are untouched: the layer dimension over pp
    mp = create_mesh({"pp": 2, "dp": 4})
    want = P("pp") if "block/" in leaf else P()
    assert spec_for(leaf, shape, PP_LM_RULES, mp) == want


def test_stacked_plan_of_the_real_tree():
    """Over ``init_params``' own tree: no leaf under ``block/`` of the
    dense model is sharded on dimension 0, and the optimizer's moments
    get their parameter's spec."""
    import jax
    import optax

    from ray_tpu.models import gpt
    from ray_tpu.parallel import create_mesh, tree_shardings, LM_RULES

    cfg = gpt.CONFIGS["nano"]
    params = jax.eval_shape(lambda k: gpt.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(optax.adamw(1e-3).init, params)
    m = create_mesh({"fsdp": 2, "tp": 2}, devices=jax.devices()[:4])
    sh = tree_shardings(params, m, LM_RULES)
    for name, leaf in sh["block"].items():
        spec = leaf["kernel"].spec if isinstance(leaf, dict) else leaf.spec
        assert not spec or spec[0] is None, (name, spec)
    assert sh["block"]["wq"]["kernel"].spec == \
        jax.sharding.PartitionSpec(None, "fsdp", "tp")
    mu = tree_shardings(opt, m, LM_RULES)[0].mu
    assert jax.tree.map(lambda a: a.spec, mu) == \
        jax.tree.map(lambda a: a.spec, sh)


def test_xla_collective_group(mesh8):
    from ray_tpu.collective import collective as C

    g = C.XlaMeshGroup("t", mesh8, "dp")
    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    assert np.allclose(np.asarray(g.allreduce(x)), x.sum(0))
    assert np.allclose(np.asarray(g.allreduce(x, "max")), x.max(0))
    assert np.allclose(np.asarray(g.allgather(x)), x)
    # global view of the scatter: row r (rank r's shard) = sum across ranks
    rs = np.asarray(g.reducescatter(np.ones((8, 4), np.float32)))
    assert rs.shape == (8, 4) and np.allclose(rs, 8.0)
    # non-sum reductions must honor ``op`` (every rank holds the same
    # replicated input, so max/min across ranks is the input itself)
    y = np.arange(32, dtype=np.float32).reshape(8, 4)
    assert np.allclose(np.asarray(g.reducescatter(y, "max")), y)
    assert np.allclose(np.asarray(g.reducescatter(y, "min")), y)
    m = np.arange(64, dtype=np.float32).reshape(8, 8)
    assert np.allclose(np.asarray(g.alltoall(m)), m.T)
    g.barrier()


def test_store_collective_group_across_actors(rt_cluster):
    rt = rt_cluster

    @rt.remote
    class Ranker:
        def __init__(self, rank, world):
            self.rank, self.world = rank, world

        def run(self):
            import numpy as np

            from ray_tpu.collective import collective as C

            g = C.StoreGroup(f"grp", self.world, self.rank)
            out = g.allreduce(np.full((4,), float(self.rank + 1)))
            bc = g.broadcast(
                np.arange(3.0) if self.rank == 0 else None, src_rank=0)
            g.barrier()
            return out.tolist(), list(np.asarray(bc))

    world = 3
    actors = [Ranker.remote(r, world) for r in range(world)]
    outs = rt.get([a.run.remote() for a in actors], timeout=60)
    for ar, bc in outs:
        assert ar == [6.0, 6.0, 6.0, 6.0]  # 1+2+3
        assert bc == [0.0, 1.0, 2.0]
