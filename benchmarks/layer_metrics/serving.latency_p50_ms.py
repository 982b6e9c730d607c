"""Median request time on the client's clock, submit to result."""
import statistics

NAME = "serving.latency_p50_ms"
LAYER = "serving"
UNIT = "ms"
MOVES = "serve_latency_p95_ms"
SOURCE = "host_clock"


def read(sources):
    lat = sources["counters"].get("latency_ms")
    return statistics.median(lat) if lat else None
