"""mfu: logical training operations per second over the chip's int8 peak.

Operations are those of ``work.train_ops_per_image`` (every conv and the
classifier, forward and both gradients, no input gradient for the stem),
at the images the traced window completed, over the window's length.  The
int8 peak is the chip's highest, the same for MLS and plain cells.
"""
from bench import work


def read(ctx):
    red, cell = ctx["trace"], ctx["cell"]
    if red.steps == 0 or red.window_s <= 0:
        return None
    cfg = cell["config"]
    images = red.steps * cell["workload"].get("batch", cfg["batch"])
    ops = images * work.train_ops_per_image(cfg)
    return 100.0 * ops / red.window_s / ctx["peaks"]["int8_ops_per_s"]
