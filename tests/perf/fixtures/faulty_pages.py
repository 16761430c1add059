"""A planted fault, for ``tests/perf``: the gpt2 architecture with an
engine whose prefix cache hands a hit its pages in the wrong order, as
a page table that was permuted would. Whole prefills are sound, so two
of the check request's four answers are right and two come from keys
and values of the wrong positions."""
import os

import perf_harness as H

_gpt2 = H.load_architecture(
    {"architecture": "gpt2"},
    here=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
vocab, model_cfg, param_shapes = _gpt2.vocab, _gpt2.model_cfg, \
    _gpt2.param_shapes
leaf_std, served_logits, reference = _gpt2.leaf_std, \
    _gpt2.served_logits, _gpt2.reference


def make_engine(params, cfg, conf):
    engine = _gpt2.make_engine(params, cfg, conf)
    lookup = engine._prefix.lookup

    def permuted(tokens):
        hist, pages = lookup(tokens)
        return hist, pages[::-1]

    engine._prefix.lookup = permuted
    return engine
