"""Each configuration's analytic FLOP count against XLA's CPU cost
analysis of one forward at the configuration's own sizes, and the
training-FLOP rule of the `mfu` reader.

Tolerance: the analytic count is two FLOPs per multiply-accumulate of the
convolutions and the classifier. XLA also counts normalization,
activations and pooling, which the analytic count leaves out (MobileNetV2
reads about 9% under XLA), and XLA leaves out the taps of a "SAME"
convolution that fall on the zero padding at the borders, which the
analytic count includes (a few % over for 3x3 convolutions on small
maps). So the two agree within 0.85x to 1.10x."""
import os

import jax
import jax.numpy as jnp
import pytest

import harness


def _config(name):
    doc = harness.load_json(os.path.join(harness.HERE, "configs",
                                         f"{name}.json"))
    ref = harness.load_module(os.path.join(harness.HERE, "configs",
                                           f"{name}.py"), f"config_{name}")
    return doc, ref


@pytest.mark.parametrize("name", ["mobilenetv2"])
def test_analytic_forward_flops_match_xla(name):
    doc, ref = _config(name)
    params = jax.eval_shape(lambda k: ref.init(doc, k), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, doc["image_size"], doc["image_size"], 3),
                             jnp.float32)
    cost = jax.jit(lambda p, x: ref.forward(doc, p, x)[0]).lower(
        params, x).compile().cost_analysis()
    ratio = sum(ref.unit_forward_flops(doc)) / cost["flops"]
    assert 0.85 <= ratio <= 1.10, ratio


@pytest.mark.parametrize("name", ["mobilenetv2"])
def test_one_count_per_freeze_unit(name):
    doc, ref = _config(name)
    assert len(ref.unit_forward_flops(doc)) == doc["freeze_units"]
    assert len(ref.unit_feature_sizes(doc)) == doc["freeze_units"] - 1


def test_train_flops_follow_the_freeze_plan():
    mfu = harness.load_module(os.path.join(harness.HERE, "metrics", "mfu.py"),
                              "metric_mfu")
    fwd = [1.0, 10.0, 100.0, 1000.0]
    assert mfu.train_flops_per_image(fwd, None) == 3 * 1111.0
    # frozen prefix: forward only; frozen after a trained unit: forward and
    # input gradient; trained: forward and both gradients
    assert mfu.train_flops_per_image(fwd, (True, False, True, False)) == \
        1.0 + 30.0 + 200.0 + 3000.0
