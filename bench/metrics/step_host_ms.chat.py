"""Host milliseconds per ``engine.step`` span less the device waits and
eager pool writes inside it, over the traced span (chat cells)."""
from bench.harness import spans


def read(run):
    return spans.read(run, spans.step_host_ms)
