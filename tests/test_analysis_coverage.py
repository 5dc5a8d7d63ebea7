"""Quantized-domain coverage auditor: classification of dots/convs,
loop/grid multipliers, and the CI gate (including the planted-fp32
negative control)."""
import json

import jax
import jax.numpy as jnp

from repro.analysis.audit import _BASELINE, apply_gate
from repro.analysis.coverage import coverage_of_jaxpr, trace_coverage
from repro.analysis.kernel_verify import find_pallas_eqns
from repro.analysis.graphs import cifar_train_graph
from repro.core import FMT_IMAGENET, QuantConfig
from repro.kernels.lowbit_conv import lowbit_conv_fused, lowbit_matmul_qd
from repro.core.lowbit import lowbit_matmul


def _qcfg(backend):
    return QuantConfig(fmt=FMT_IMAGENET, backend=backend, stochastic=False,
                       k_block=32, pallas_interpret=True)


def test_pallas_matmul_grad_fully_quantized():
    cfg = _qcfg("pallas")

    def loss(x, w):
        return lowbit_matmul_qd(x, w, None, cfg).sum()

    args = (jax.ShapeDtypeStruct((64, 96), jnp.float32),
            jax.ShapeDtypeStruct((96, 128), jnp.float32))
    rep = trace_coverage(jax.grad(loss, argnums=(0, 1)), *args)
    assert rep.quantized_macs > 0
    assert rep.full_precision_macs == 0
    assert rep.quantized_fraction == 1.0
    # all three training GEMMs (fwd, dgrad, wgrad) visible: three GEMM
    # kernels, each contracting its scaling groups with quantized dots
    cj = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(*args)
    gemms = [e for e in find_pallas_eqns(cj.jaxpr)
             if any(q.primitive.name == "dot_general"
                    for q in e.params["jaxpr"].eqns)]
    assert len(gemms) == 3
    assert all(s.klass == "quantized" for s in rep.sites if s.kind == "dot")


def test_fake_quant_backend_is_full_precision():
    cfg = QuantConfig(fmt=FMT_IMAGENET, stochastic=False)  # fake_quant

    def loss(x, w):
        return lowbit_matmul(x, w, None, cfg).sum()

    rep = trace_coverage(
        jax.grad(loss, argnums=(0, 1)),
        jax.ShapeDtypeStruct((64, 96), jnp.float32),
        jax.ShapeDtypeStruct((96, 128), jnp.float32),
    )
    assert rep.quantized_macs == 0
    assert rep.full_precision_macs > 0
    assert rep.quantized_fraction == 0.0


def test_scan_length_multiplies_macs():
    def f(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None

        h, _ = jax.lax.scan(body, x, None, length=5)
        return h

    rep = trace_coverage(
        f,
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 16), jnp.float32),
    )
    assert rep.full_precision_macs == 5 * 8 * 16 * 16
    assert any("scan[5]" in s.path for s in rep.sites)


def test_im2col_patch_convs_are_data_movement():
    cfg = _qcfg("pallas")

    def loss(x, w):
        return lowbit_conv_fused(x, w, None, (1, 1), "SAME", cfg).sum()

    rep = trace_coverage(
        jax.grad(loss, argnums=(0, 1)),
        jax.ShapeDtypeStruct((2, 8, 8, 8), jnp.float32),
        jax.ShapeDtypeStruct((16, 8, 3, 3), jnp.float32),
    )
    convs = [s for s in rep.sites if s.kind == "conv"]
    assert convs, "expected im2col patch-extraction convs in the trace"
    assert all(s.klass == "data_movement" for s in convs)
    assert rep.quantized_fraction == 1.0  # GEMMs quantized, convs excluded


def _gate_report(cov):
    return {
        "graphs": {
            "train:resnet20": {
                "coverage": cov.to_json(),
                "lint": {"ok": True, "errors": [], "warnings": []},
            }
        }
    }


def test_resnet20_train_step_meets_coverage_gate():
    cov = coverage_of_jaxpr(cifar_train_graph(backend="pallas").jaxpr())
    assert cov.quantized_fraction >= 0.99, cov.to_json()
    # stem conv + classifier are unquantized by design, so fp32 > 0
    assert cov.full_precision_macs > 0
    assert cov.data_movement_macs > 0  # im2col patch gathers reported apart
    with open(_BASELINE) as f:
        baseline = json.load(f)
    assert apply_gate(_gate_report(cov), baseline) == []


def test_gate_catches_planted_fp32_dot():
    g = cifar_train_graph(backend="pallas", sabotage=True)
    cov = coverage_of_jaxpr(g.jaxpr())
    assert cov.quantized_fraction < 0.99
    with open(_BASELINE) as f:
        baseline = json.load(f)
    failures = apply_gate(_gate_report(cov), baseline)
    assert failures and "train:resnet20" in failures[0]
    # the report names the planted dot as the largest fp32 site
    assert "'kind': 'dot'" in failures[0]


def test_hlo_parser_compat_shim():
    from repro.analysis import hlo_parser
    from repro.launch import hlo_analysis

    assert hlo_analysis.analyze_hlo is hlo_parser.analyze_hlo
    res = hlo_parser.analyze_hlo("")
    assert "dot_flops_by_dtype" in res and "coll_breakdown" in res
