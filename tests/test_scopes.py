"""Layer scopes in the compiled train step.

The program names its layers with ``jax.named_scope`` so that a profile of
the compiled step can put each device op under one layer: ``conv``,
``fc``, ``bn``, ``act``, ``loss`` and ``optimizer`` in the model and its
step, ``im2col``, ``col2im``, ``rounding``, ``quantize`` and ``gemm``
inside the MLS conv.  A scope reaches the HLO as ``op_name`` metadata; the
innermost scope word of an ``op_name`` is the op's layer.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import FMT_IMAGENET, QuantConfig
from repro.kernels.lowbit_conv import lowbit_conv_fused
from repro.models.cnn import CNNConfig, init_cnn
from repro.optim import sgdm_init
from repro.train import make_cnn_train_step

MODEL_SCOPES = ("conv", "fc", "bn", "act", "loss", "optimizer")
KERNEL_SCOPES = ("im2col", "col2im", "rounding", "quantize", "gemm")
VOCABULARY = MODEL_SCOPES + KERNEL_SCOPES

_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# name = <type> <op>(...): a tuple type holds no space-led lowercase word
# before a parenthesis, so the first one is the op
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\s([a-z][\w\-]*)\(")


def innermost_scope(op_name: str) -> str | None:
    words = [t for t in re.findall(r"[A-Za-z_]\w*", op_name)
             if t in VOCABULARY]
    return words[-1] if words else None


def compiled_plain_step(batch: int = 8) -> str:
    cfg = CNNConfig(arch="resnet20", num_classes=10, in_hw=32)
    params = jax.eval_shape(lambda k: init_cnn(k, cfg), jax.random.key(0))
    args = (params, jax.eval_shape(sgdm_init, params),
            {"image": jax.ShapeDtypeStruct((batch, 3, 32, 32), jnp.float32),
             "label": jax.ShapeDtypeStruct((batch,), jnp.int32)},
            jax.ShapeDtypeStruct((), jnp.int32))
    step = make_cnn_train_step(cfg, None, 0.1, rounding_seed=0)
    return step.lower(*args).compile().as_text()


def strip_metadata(hlo_text: str) -> list[str]:
    """HLO text without instruction metadata and the module's stack-frame
    tables."""
    text = re.sub(r",? metadata=\{[^{}]*\}", "", hlo_text)
    tables = {"FileNames", "FunctionNames", "FileLocations", "StackFrames"}
    out, in_table = [], False
    for line in text.splitlines():
        if line.strip() in tables:
            in_table = True
        elif not (in_table and re.match(r"^\d+ ", line)):
            in_table = False
            out.append(line)
    return out


@pytest.fixture(scope="module")
def plain_hlo() -> str:
    return compiled_plain_step()


def test_plain_step_ops_each_map_to_one_scope(plain_hlo):
    """Every fusion, convolution and reduce that the step's own code made
    falls under a layer."""
    seen, lost = set(), []
    for line in plain_hlo.splitlines():
        m, meta = _INSTR.match(line), _OP_NAME.search(line)
        if m is None or meta is None or m.group(2) not in (
                "fusion", "convolution", "reduce"):
            continue
        if not meta.group(1).startswith("jit(step)/"):
            continue
        scope = innermost_scope(meta.group(1))
        if scope is None:
            lost.append((m.group(1), meta.group(1)))
        else:
            seen.add(scope)
    assert lost == []
    assert seen, "no fusion, convolution or reduce carries the step's metadata"


def test_plain_step_names_every_model_layer(plain_hlo):
    scopes = {innermost_scope(n) for n in _OP_NAME.findall(plain_hlo)
              if n.startswith("jit(step)/")}
    assert set(MODEL_SCOPES) <= scopes


def test_scopes_change_only_metadata(plain_hlo, monkeypatch):
    """The step compiled without any scope is the same program."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled_plain_step()
    assert "jvp(conv)" not in bare and "jvp(conv)" in plain_hlo
    assert strip_metadata(bare) == strip_metadata(plain_hlo)


def _mls_conv_grad(conv_impl: str):
    """Forward and both gradients of one MLS conv, and its inputs."""
    cfg = QuantConfig(fmt=FMT_IMAGENET, k_block=18, grouping="nc",
                      stochastic=True, backend="pallas", pallas_interpret=True,
                      conv_impl=conv_impl, block_n=8)

    def loss(x, w, key):
        return lowbit_conv_fused(x, w, key, (1, 1), "SAME", cfg).sum()

    fn = jax.value_and_grad(loss, argnums=(0, 1))
    return fn, (jnp.ones((2, 4, 6, 6)), jnp.ones((6, 4, 3, 3)),
                jax.random.key(0))


@pytest.mark.parametrize("conv_impl", ["implicit", "im2col"])
def test_mls_conv_carries_kernel_scopes(conv_impl):
    fn, args = _mls_conv_grad(conv_impl)
    text = jax.jit(fn).lower(*args).as_text(dialect="hlo", debug_info=True)
    scopes = {innermost_scope(n) for n in _OP_NAME.findall(text)}
    assert set(KERNEL_SCOPES) <= scopes


def _pallas_names(jaxpr) -> list[str]:
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names += _pallas_names(inner)
    return names


def test_pallas_kernels_are_named():
    fn, args = _mls_conv_grad("implicit")
    names = set(_pallas_names(jax.make_jaxpr(fn)(*args).jaxpr))
    assert names == {"mls_quantize", "mls_matmul", "implicit_conv"}
