"""A second architecture, for ``tests/perf``: added to a copy of the
benchmark as ``architectures/dummy.py`` with ``dummy_reference.py``
beside it. It is the nano GPT under other key names in the
configuration's ``model`` block, with a reference of its own: what a
PR that adds a model brings, as files. Like such a PR's file it imports
the program's model code openly (inside its functions: the driver of a
serving cell loads this module and stays off jax), and its
configuration is a CUT one (``dummy-serve.json``: ``reduced``, ``cut``,
``cut_stands_for``)."""
import functools
import os

import perf_harness as H

_HERE = os.path.dirname(os.path.abspath(__file__))
_gpt2 = H.load_architecture({"architecture": "gpt2"},
                             here=os.path.dirname(_HERE))

param_shapes, leaf_std = _gpt2.param_shapes, _gpt2.leaf_std
make_engine, served_logits = _gpt2.make_engine, _gpt2.served_logits
train_program = _gpt2.train_program


def vocab(conf):
    return conf["model"]["vocab"], conf["model"]["rows"]


def model_cfg(conf):
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    m = conf["model"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return gpt.GPTConfig(
        vocab_size=m["rows"], n_layer=m["layers"], n_head=m["heads"],
        d_model=m["width"], d_ff=m["ffn"], max_seq=m["positions"],
        dtype=dtypes[conf["numerics"]["compute_dtype"]],
        param_dtype=dtypes[conf["numerics"]["param_dtype"]],
        remat=conf.get("train", {}).get("remat", "dots"),
        loss_chunk=conf.get("train", {}).get("loss_chunk", 0))


def reference(cfg):
    ref = H.load_file(os.path.join(_HERE, "dummy_reference.py"),
                      "perf_arch_")
    return (ref.from_program,
            functools.partial(ref.forward, heads=cfg.n_head),
            functools.partial(ref.loss, heads=cfg.n_head))
