"""gemm_roofline: the least time the quantized GEMMs of one step could take
(the larger of ``work.gemm_work``'s operations over the int8 peak and its
bytes over the HBM bandwidth) over their measured device time per step."""
from bench import work


def read(ctx):
    cell = ctx["cell"]
    ms = ctx["trace"].ms_per_step("gemm")
    if ms is None:
        return None
    cfg, wl = cell["config"], cell["workload"]
    bits = 1 + wl["fmt"][0] + wl["fmt"][1]
    ops, nbytes = work.gemm_work(cfg, wl.get("batch", cfg["batch"]), bits)
    peaks = ctx["peaks"]
    least = max(ops / peaks["int8_ops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
