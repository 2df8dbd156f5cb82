"""idle_engine_host_share (layer: engine host, serving/engine.py): the
device's idle time, in % of the traced window, while the host was in
the engine's own work: ``repro/admit``, a phase's ``prepare`` and
``emit`` (the client's token callbacks, commit, finish, the
controller), ``repro/prefix_*``, and ``repro/step`` outside them
(the program's spans on the trace's clock, bench/spans.py)."""
from bench import spans


def read(run):
    return spans.share(run, "engine_host")
