"""90th percentile over the requests due in the window of the
milliseconds from submission to first admission
(``RequestHandle.queued_s``), beside ``ttft_p90_s``; in a traced run, up
to the traced span's end (chat cells)."""
from bench.harness import spans


def read(run):
    return spans.queue_wait_ms(run, 90)
