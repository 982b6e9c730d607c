"""Median time of one optimizer step over the window: host clock between
the completions of consecutive steps (the loop blocks on each step's loss
``max_in_flight`` steps behind the dispatch)."""
import statistics

NAME = "train_step.step_ms"
LAYER = "train step"
UNIT = "ms"
MOVES = "train_images_per_s_per_chip"
SOURCE = "host_clock"


def read(sources):
    steps = sources["counters"].get("step_ms")
    return statistics.median(steps) if steps else None
