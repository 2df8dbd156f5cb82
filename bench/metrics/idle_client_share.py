"""idle_client_share (layer: client, bench/client.py): the device's idle
time, in % of the traced window, outside every ``repro/step`` span:
the harness's own code between engine steps (sending, recording,
sleeping until a request is due), on the trace's clock
(bench/spans.py)."""
from bench import spans


def read(run):
    return spans.share(run, "client")
