"""Wire-byte budget audit of the MLS-compressed cross-pod gradient ring.

Lowers ``parallel.compress.crosspod_allreduce_mean`` under ``shard_map`` on
an ``n_pods``-wide mesh, compiles it (AOT, nothing executed), and feeds the
post-optimization HLO to :mod:`repro.analysis.hlo_parser` to count the
actual collective-permute bytes per device.  The compressed ring must move

    per hop:  n codes (1 B) + n/block group scales (4 B) + 1 tensor scale

instead of the fp32 ring's ``4n`` bytes per hop — a ~3.88x reduction for
block=128.  The audit asserts the *compiled* graph achieves this: a
regression (XLA upcasting the codes, an accidental fp32 exchange, scales
blown up to full shape) shows up as a collapsed compression ratio.

Requires >= n_pods devices; the CLI forces host devices via XLA_FLAGS
(``--xla_force_host_platform_device_count``) before first JAX backend use.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.analysis.hlo_parser import analyze_hlo
from repro.core import FMT_IMAGENET, EMFormat

__all__ = ["audit_wire_ring"]


def audit_wire_ring(
    n_elems: int = 1 << 16,
    n_pods: int = 2,
    fmt: EMFormat = FMT_IMAGENET,
    block: int = 128,
) -> dict:
    """AOT-compile the compressed ring and report wire bytes per device."""
    if len(jax.devices()) < n_pods:
        raise RuntimeError(
            f"wire audit needs {n_pods} devices, have {len(jax.devices())}; "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n_pods} "
            f"before JAX initializes its backend"
        )
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.parallel.compress import crosspod_allreduce_mean

    mesh = make_mesh((n_pods,), ("pod",))

    @partial(jax.shard_map, mesh=mesh, in_specs=P("pod", None),
             out_specs=P("pod", None))
    def ring(x):  # x: (1, n_elems) per pod
        return crosspod_allreduce_mean(x[0], "pod", fmt=fmt)[None]

    g = jax.ShapeDtypeStruct((n_pods, n_elems), jnp.float32)
    compiled = jax.jit(ring).lower(g).compile()
    hlo = compiled.as_text()
    res = analyze_hlo(hlo)

    actual = res["coll_breakdown"].get("collective-permute", 0.0)
    breakdown = {
        k.split(":", 1)[1]: v
        for k, v in res["coll_breakdown"].items()
        if k.startswith("collective-permute:")
    }
    # fp32 ring moving the same gradient: (p-1) hops of 4n bytes
    fp32_ring = 4.0 * n_elems * (n_pods - 1)
    # ideal compressed payload (codes + group scales + tensor scale)
    ideal = (n_elems + 4.0 * n_elems / block + 4.0) * (n_pods - 1)
    ratio = fp32_ring / actual if actual else 0.0
    return {
        "n_elems": n_elems,
        "n_pods": n_pods,
        "fmt": str(fmt),
        "block": block,
        "wire_bytes_per_device": actual,
        "wire_bytes_by_dtype": breakdown,
        "fp32_ring_bytes_per_device": fp32_ring,
        "ideal_compressed_bytes_per_device": ideal,
        "compression_ratio": ratio,
        "n_collective_permutes": res["coll_counts"].get(
            "collective-permute", 0
        ),
    }
