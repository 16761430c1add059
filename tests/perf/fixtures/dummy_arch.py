"""A second architecture, for ``tests/perf``: added to a copy of the
benchmark as ``architectures/dummy.py`` with ``dummy_reference.py``
beside it. It is the nano GPT under other key names in the
configuration's ``model`` block, with a reference of its own: what a
PR that adds a model brings, as files."""
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
    m = conf["model"]
    return _gpt2.model_cfg(dict(conf, model={
        "n_layer": m["layers"], "n_embd": m["width"],
        "n_head": m["heads"], "n_inner": m["ffn"],
        "n_positions": m["positions"], "vocab_size": m["vocab"],
        "embedding_rows_held": m["rows"]}))


def reference(cfg):
    ref = H.load_file(os.path.join(_HERE, "dummy_reference.py"),
                      "perf_arch_")
    return (ref.from_program,
            functools.partial(ref.forward, heads=cfg.n_head),
            functools.partial(ref.loss, heads=cfg.n_head))
