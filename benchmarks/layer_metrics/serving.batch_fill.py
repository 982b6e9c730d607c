"""Rows served over rows of the buckets dispatched (the program's
ServingMeter ``fill_ratio``): what the power-of-two buckets pad away."""
NAME = "serving.batch_fill"
LAYER = "serving"
UNIT = "%"
MOVES = "serve_images_per_s"
SOURCE = "program_counter"


def read(sources):
    fill = (sources.get("meter") or {}).get("fill_ratio")
    return None if fill is None or fill != fill else 100.0 * fill
