"""The yardstick's counts and the benchmark's parameter tree."""
import json
import os

import jax
import pytest

from bench import model, work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_resnet20_forward_macs():
    # stem 442,368 + stage 1 14,155,776 + stage 2 13,107,200
    # + stage 3 13,107,200 + classifier 640
    assert work.forward_macs(_config("resnet20-cifar")) == 40_813_184


def test_resnet18_forward_macs():
    macs = work.forward_macs(_config("resnet18-imagenet"))
    assert macs == pytest.approx(1.82e9, rel=0.005)


@pytest.mark.parametrize("name", ["resnet20-cifar", "resnet18-imagenet"])
def test_conv_macs_match_program_count(name):
    from repro.models.cnn import CNNConfig, count_ops

    cfg = _config(name)
    ops = count_ops(CNNConfig(arch=cfg["arch"], num_classes=cfg["num_classes"],
                              in_hw=cfg["in_hw"]))
    convs = [d for kind, d in ops if kind == "conv"]
    assert sum(c.macs for c in model.convs(cfg)) == sum(
        d["c_in"] * d["c_out"] * d["k"] ** 2 * d["h"] * d["w"] for d in convs)
    assert len(convs) == len(model.convs(cfg))


def test_train_ops_count_three_gemms_but_no_stem_input_gradient():
    cfg = _config("resnet20-cifar")
    stem = model.convs(cfg)[0].macs
    assert work.train_ops_per_image(cfg) == 2 * (3 * work.forward_macs(cfg)
                                                 - stem)


def test_quantize_and_gemm_work_of_one_stage_by_hand():
    # stem 3 -> 16 (float), then one block of two 16 -> 16 3x3 convs at 8x8
    cfg = dict(_config("resnet20-cifar"), depths=[1], widths=[16], in_hw=8)
    x = e = 2 * 16 * 8 * 8  # batch 2
    w = 16 * 16 * 9
    per_conv_q = 4 * (x + w + e) + 2 * (x + w + e) * 4 / 8
    assert work.quantize_bytes(cfg, 2, 4) == 2 * per_conv_q
    ops, nbytes = work.gemm_work(cfg, 2, 4)
    assert ops == 2 * 3 * 2 * (2 * 8 * 8 * 16 * 16 * 9)
    assert nbytes == 2 * (2 * (x + w + e) * 4 / 8 + 4 * (x + w + e))


@pytest.mark.parametrize("name", ["resnet20-cifar", "resnet18-imagenet"])
def test_parameter_tree_is_the_programs(name):
    from repro.models.cnn import CNNConfig, init_cnn

    cfg = _config(name)
    ours = jax.eval_shape(lambda k: model.init_params(k, cfg),
                          jax.random.key(0))
    theirs = jax.eval_shape(
        lambda k: init_cnn(k, CNNConfig(arch=cfg["arch"],
                                        num_classes=cfg["num_classes"],
                                        in_hw=cfg["in_hw"])),
        jax.random.key(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(ours)] == [
        (b.shape, b.dtype) for b in jax.tree.leaves(theirs)]
