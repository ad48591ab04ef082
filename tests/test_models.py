"""Model zoo + driver-hook smoke tests (virtual 8-CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_mnist_cnn_shapes():
    from horovod_tpu.models import MnistCNN

    model = MnistCNN()
    x = jnp.ones((4, 28, 28, 1))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (4, 10)
    assert logits.dtype == jnp.float32


def test_tiny_resnet_shapes_and_bn():
    from horovod_tpu.models.resnet import BottleneckBlock, ResNet

    model = ResNet(stage_sizes=[1, 1], block_cls=BottleneckBlock,
                   num_classes=7, num_filters=8, dtype=jnp.float32,
                   small_inputs=True)
    x = jnp.ones((2, 8, 8, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    assert "batch_stats" in variables
    logits, updated = model.apply(variables, x, train=True,
                                  mutable=["batch_stats"])
    assert logits.shape == (2, 7)
    # Running statistics actually move in train mode.
    before = jax.tree_util.tree_leaves(variables["batch_stats"])
    after = jax.tree_util.tree_leaves(updated["batch_stats"])
    assert any(not np.allclose(b, a) for b, a in zip(before, after))


def test_resnet50_param_count():
    """ResNet-50 must be the real architecture: ~25.6M parameters, matching
    the keras/torchvision models the reference examples train."""
    from horovod_tpu.models import ResNet50

    model = ResNet50(num_classes=1000)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, 224, 224, 3)), train=False))
    n = sum(int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(variables["params"]))
    assert 25.4e6 < n < 25.8e6, n


def test_vgg16_param_count():
    """VGG-16: ~138.36M parameters (the parameter-heavy benchmark of the
    reference's scaling table, /root/reference/docs/benchmarks.md:6)."""
    from horovod_tpu.models import VGG16

    model = VGG16(num_classes=1000)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, 224, 224, 3)), train=False))
    n = sum(int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(variables["params"]))
    assert 138.0e6 < n < 138.7e6, n


def test_inception_v3_param_count_and_shape():
    """Inception V3: ~23.8M parameters (sans aux head), 299x299 input
    (the reference's 90%-efficiency benchmark, docs/benchmarks.md:5)."""
    from horovod_tpu.models import InceptionV3

    model = InceptionV3(num_classes=1000)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, 299, 299, 3)), train=False))
    n = sum(int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(variables["params"]))
    assert 23.0e6 < n < 24.5e6, n
    out = jax.eval_shape(
        lambda: model.init_with_output(
            jax.random.PRNGKey(0), jnp.ones((2, 299, 299, 3)),
            train=False)[0])
    assert out.shape == (2, 1000)


def test_vgg_tiny_forward():
    from horovod_tpu.models.vgg import VGG

    model = VGG(stage_convs=(1, 1), num_classes=5, dtype=jnp.float32)
    x = jnp.ones((2, 16, 16, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 5)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.slow  # ~25s; the shard_map DP training step stays tier-1 in
# test_transformer.py::test_dp_sp_train_step (allreduce-averaged grads
# over a device mesh) and driver hooks keep calling dryrun directly
def test_dryrun_multichip_8():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_space_to_depth_stem_is_exact():
    """SpaceToDepthStem is the 7x7/stride-2 SAME conv *exactly* (same
    parameter, reshaped weights), on both even (s2d) and odd (plain-conv
    fallback) input sizes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from horovod_tpu.models.resnet import SpaceToDepthStem

    stem = SpaceToDepthStem(features=8, dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 16, 3),
                    jnp.float32)
    params = stem.init(jax.random.PRNGKey(0), x)
    w = params["params"]["kernel"]
    for shape in ((2, 16, 16, 3), (1, 15, 15, 3)):
        xi = jnp.asarray(np.random.RandomState(1).randn(*shape), jnp.float32)
        want = lax.conv_general_dilated(
            xi, w, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        got = stem.apply(params, xi)
        np.testing.assert_allclose(got, want, atol=2e-6, err_msg=str(shape))


@pytest.mark.slow  # ~27s; BN semantics stay tier-1 in
# test_tiny_resnet_shapes_and_bn, the fused step in
# test_packed_train_step_bit_identical
def test_fused_ema_batchnorm_matches_flax_bn():
    """ResNet(fused_ema=True) + ema_batch_stats reproduces the stock flax
    BatchNorm path exactly (same logits, same running stats) over several
    training steps — the EMA is hoisted out of the 104 BN layers into one
    fused op, not changed (models/norm.py)."""
    import optax

    from horovod_tpu.models import ResNet18, ema_batch_stats

    def run(fused):
        model = ResNet18(num_classes=10, dtype=jnp.float32,
                         small_inputs=True, fused_ema=fused)
        images = jnp.asarray(
            np.random.RandomState(0).rand(4, 32, 32, 3), jnp.float32)
        labels = jnp.asarray([0, 1, 2, 3], jnp.int32)
        variables = model.init(jax.random.PRNGKey(0), images, train=False)
        params, stats = variables["params"], variables["batch_stats"]
        tx = optax.sgd(0.1)
        opt_state = tx.init(params)

        def loss_fn(p, stats):
            logits, upd = model.apply(
                {"params": p, "batch_stats": stats}, images, train=True,
                mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            return loss, upd["batch_stats"]

        for _ in range(3):
            (loss, new_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, stats)
            stats = (ema_batch_stats(stats, new_stats, 0.9) if fused
                     else new_stats)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        eval_logits = model.apply({"params": params, "batch_stats": stats},
                                  images, train=False)
        return loss, stats, eval_logits

    loss_a, stats_a, eval_a = run(False)
    loss_b, stats_b, eval_b = run(True)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        stats_a, stats_b)
    np.testing.assert_allclose(eval_a, eval_b, rtol=1e-4, atol=1e-5)
