"""The benchmark's tests run on the CPU, from the root of the checkout."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture
def tiny_cell():
    """A cell of the ResNet-20 configuration at a size the CPU can run:
    width 0.25, 8x8 inputs, batch 8, the workload's own limits."""
    from bench import run

    def make(numerics: str) -> dict:
        cell = run.load_cell(f"resnet20-cifar.{numerics}")
        cell["name"] = f"tiny.{numerics}"
        cell["config"].update(width_mult=0.25, in_hw=8, batch=8)
        return cell

    return make
