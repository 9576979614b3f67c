"""Plain reference for the bottleneck ResNet v1.5 (He et al. 2015 with the
stride of a down-sampling block on its 3x3 convolution): weights from a
seed and the forward pass in float32 ``jax.numpy``, NHWC, no kernels and no
batching tricks. Imports nothing of the program under test.

``cast`` is applied to both operands of every convolution and of the
classifier: the identity for the reference; a round trip through a lower
precision for the control that the comparison must reject.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references._control import identity, lower_precision

MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)


def make_weights(sizes, seed):
    """He-initialised float32 pytree; batch norm in inference form is a
    per-channel scale and bias, here 1 and 0."""
    rng = np.random.default_rng(seed)
    width, classes = sizes["width"], sizes["num_classes"]

    def conv(kh, kw, cin, cout):
        w = rng.normal(0, np.sqrt(2.0 / (kh * kw * cin)), (kh, kw, cin, cout))
        return {"w": w.astype(np.float32), "scale": np.ones(cout, np.float32),
                "bias": np.zeros(cout, np.float32)}

    params = {"stem": conv(7, 7, 3, width), "stages": []}
    cin = width
    for si, nblocks in enumerate(sizes["stage_sizes"]):
        cmid, stage = width * 2 ** si, []
        for bi in range(nblocks):
            blk = {"conv1": conv(1, 1, cin, cmid),
                   "conv2": conv(3, 3, cmid, cmid),
                   "conv3": conv(1, 1, cmid, 4 * cmid)}
            if bi == 0:
                blk["proj"] = conv(1, 1, cin, 4 * cmid)
            stage.append(blk)
            cin = 4 * cmid
        params["stages"].append(stage)
    params["head"] = {
        "w": rng.normal(0, 0.01, (cin, classes)).astype(np.float32),
        "b": np.zeros(classes, np.float32)}
    return params


def forward(params, images_u8, cast=identity):
    """uint8 NHWC images -> float32 logits."""
    x = (images_u8.astype(jnp.float32) / 255.0 - jnp.asarray(MEAN)) \
        / jnp.asarray(STD)

    def conv_bn(x, p, stride=1):
        k = p["w"].shape[0]
        y = jax.lax.conv_general_dilated(
            cast(x), cast(p["w"]), (stride, stride),
            [(k // 2, k // 2), (k // 2, k // 2)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
        return y * p["scale"] + p["bias"]

    x = jax.nn.relu(conv_bn(x, params["stem"], 2))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    for si, stage in enumerate(params["stages"]):
        for bi, blk in enumerate(stage):
            stride = 2 if (si > 0 and bi == 0) else 1
            y = jax.nn.relu(conv_bn(x, blk["conv1"]))
            y = jax.nn.relu(conv_bn(y, blk["conv2"], stride))
            y = conv_bn(y, blk["conv3"])
            shortcut = conv_bn(x, blk["proj"], stride) if "proj" in blk else x
            x = jax.nn.relu(y + shortcut)
    feat = jnp.mean(x, axis=(1, 2))
    return jnp.dot(cast(feat), cast(params["head"]["w"]),
                   precision=jax.lax.Precision.HIGHEST) + params["head"]["b"]


def logits(params, images_u8, control=None):
    cast = lower_precision(control)
    return np.asarray(jax.jit(lambda w, im: forward(w, im, cast))(
        params, jnp.asarray(images_u8)))
