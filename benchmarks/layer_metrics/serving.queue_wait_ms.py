"""Mean time a request waits from enqueue until its batch is coalesced:
the ``coalesce`` phase of the program's ServingMeter."""
NAME = "serving.queue_wait_ms"
LAYER = "serving"
UNIT = "ms"
MOVES = "serve_latency_p95_ms"
SOURCE = "program_span"


def read(sources):
    meter = sources.get("meter") or {}
    return (meter.get("phase_ms") or {}).get("coalesce")
