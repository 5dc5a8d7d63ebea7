"""The runs that must fail closed, without a chip."""
import dataclasses

import jax
import numpy as np
import pytest

from bench import run, trace


def test_no_tpu_fails_closed(capsys):
    rc = run.main(["--workload", "resnet20-cifar.plain", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU found" in err
    assert out == ""


@dataclasses.dataclass
class _FakeChip:
    platform: str = "tpu"
    device_kind: str = "TPU v99 imaginary"


def test_unknown_chip_kind_fails_closed(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeChip()])
    with pytest.raises(run.BenchError, match="not in bench/peaks.json"):
        run.check_device(1)


def test_too_few_chips_fail_closed(monkeypatch):
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeChip(device_kind="TPU v5 lite")])
    with pytest.raises(run.BenchError, match="needs 4 chips"):
        run.check_device(4)


def test_missing_workload_fails_closed():
    with pytest.raises(run.BenchError, match="no workload file"):
        run.load_cell("no-such-cell")


def test_compile_inside_the_window_fails_closed(monkeypatch, tiny_cell):
    real = run.jit_program

    def compiling(*args, **kwargs):
        step = real(*args, **kwargs)
        calls = []

        def call(*a):
            calls.append(1)
            # a new program on every call: compiles inside the window
            jax.jit(lambda x, n=len(calls): x + n)(np.float32(0))
            return step(*a)

        return call

    monkeypatch.setattr(run, "jit_program", compiling)
    with pytest.raises(run.BenchError, match="inside the window"):
        run.run_cell(tiny_cell("plain"), 3, 0.2, False,
                     dev=jax.devices()[0])


def test_unexpected_custom_call_is_reported_and_counted_as_gemm():
    hlo = (
        '  %q.1 = (u8[8,128]{1,0}, f32[8,1]{1,0}) custom-call(%a), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{f32[8,128]{1,0}, u8[8,128]{1,0}}, metadata={}\n'
        '  %g.2 = f32[8,128]{1,0} custom-call(%b, %c), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{u8[8,128]{1,0}, u8[128,128]{1,0}}, metadata={}\n'
        '  %odd.3 = f32[8,128]{1,0} custom-call(%d), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{f32[8,128]{1,0}}, metadata={}\n'
        '  %fusion.4 = f32[8,128]{1,0} fusion(%e), kind=kLoop\n')
    classes, unexpected = trace.classify_hlo(hlo)
    assert classes == {"q.1": "quantize", "g.2": "gemm", "odd.3": "gemm"}
    assert unexpected == ["odd.3"]
