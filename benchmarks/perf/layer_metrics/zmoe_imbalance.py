"""How uneven the routing is over the routed experts held, where part
of the choices fall on identity experts: the fullest held expert's
tokens over the mean of the touched ones, a decode step a layer, as
``moe_imbalance`` reads the engine's counters. 1 is even; the layer is
dropless, so an uneven step costs the fullest expert's blocks and
drops nothing; at 2 tokens an expert the chance spread alone reads
about 2.5.
"""
from perf_harness import load_reader

LAYER = "expert layer"
UNIT = "x"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(run):
    return load_reader("moe_imbalance").read(run)
