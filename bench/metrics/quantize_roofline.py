"""quantize_roofline: the least time the quantizers of one step could take
(``work.quantize_bytes`` over the chip's HBM bandwidth) over their
measured device time per step."""
from bench import work


def read(ctx):
    cell = ctx["cell"]
    ms = ctx["trace"].ms_per_step("quantize")
    if ms is None:
        return None
    cfg, wl = cell["config"], cell["workload"]
    bits = 1 + wl["fmt"][0] + wl["fmt"][1]
    nbytes = work.quantize_bytes(cfg, wl.get("batch", cfg["batch"]), bits)
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / (ms * 1e-3)
