"""Median host time per step from handing a host batch to
``shard_batch_to_mesh`` until the step's dispatch returns."""
import statistics

NAME = "input.host_feed_ms"
LAYER = "input"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "host_clock"


def read(sources):
    feed = sources["counters"].get("host_feed_ms")
    return statistics.median(feed) if feed else None
