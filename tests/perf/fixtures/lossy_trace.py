"""Planted faults, for ``tests/perf``: the gpt2 architecture whose
replica loses its traced slice, or is taken for another at the run's
end. ``make_engine`` runs inside the replica, so what it plants lands
in the process that traces. The configuration's ``"plant"`` says which:

``"trace_stop_raises"``
    ``Tracer.stop`` ends the trace and raises, as a profiler that could
    not write its file would: ``trace_stop`` fails through the handle,
    the slice is not brought home.
``"pid_differs"``
    ``report()`` (and nothing else) reads another process id from its
    second call on: the trace comes home, and the replica that answers
    at the end is not the one the run began with.
"""
import os
import sys

import perf_harness as H

_gpt2 = H.load_architecture(
    {"architecture": "gpt2"},
    here=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
vocab, model_cfg, param_shapes = _gpt2.vocab, _gpt2.model_cfg, \
    _gpt2.param_shapes
leaf_std, served_logits, reference = _gpt2.leaf_std, \
    _gpt2.served_logits, _gpt2.reference


def make_engine(params, cfg, conf):
    import perf_deployment

    sound = perf_deployment.Tracer.stop
    getpid, reports = os.getpid, []

    def stop_and_raise(self):
        sound(self)
        raise RuntimeError("planted: the profiler could not\nstop")

    def another_pid():
        if sys._getframe(1).f_code.co_name != "report":
            return getpid()
        reports.append(1)
        return getpid() + (len(reports) > 1)

    if conf["plant"] == "trace_stop_raises":
        perf_deployment.Tracer.stop = stop_and_raise
    elif conf["plant"] == "pid_differs":
        os.getpid = another_pid
    else:
        raise ValueError(conf["plant"])
    return _gpt2.make_engine(params, cfg, conf)
