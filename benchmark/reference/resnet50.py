"""Plain ResNet-50 forward pass: ``jax.numpy`` and ``lax`` in float32,
inference batch-norm, no code of the program's importer. Every product
runs at ``precision=HIGHEST`` (true float32), or, for the second
comparison, at the device's default (on the TPU: float32 operands
rounded to bfloat16, float32 accumulation), which is the precision the
configuration runs its float32 graph in. Follows He et al. 2015 (bottleneck blocks,
projection shortcuts where the shape changes) with the stride in the
3x3 convolution, which is the graph ``builders/resnet50_onnx.py``
writes (torchvision's "v1.5" placement; the paper strides in the first
1x1). Weights are the builder's seeded dict.
"""

import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = {"highest": lax.Precision.HIGHEST,
              "default": lax.Precision.DEFAULT}


def forward(weights, x, stages, precision, epsilon=1e-5):
    """``x``: ``(n, 3, side, side)`` float32 -> ``(n, classes)`` logits."""
    names = iter(sorted((n[:-2] for n in weights if n.endswith(".w")
                         and n != "fc.w"), key=lambda n: int(n[1:])))

    def conv_bn(h, k, stride, relu):
        name = next(names)
        pad = k // 2
        h = lax.conv_general_dilated(
            h, weights[name + ".w"], (stride, stride),
            [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=precision)

        def per_channel(p):
            return weights[f"{name}.{p}"][None, :, None, None]

        h = ((h - per_channel("mean"))
             / jnp.sqrt(per_channel("var") + epsilon)
             * per_channel("scale") + per_channel("bias"))
        return jnp.maximum(h, 0.0) if relu else h

    h = conv_bn(x, 7, 2, True)
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    cin = h.shape[1]
    for stage, (blocks, cmid) in enumerate(stages):
        cout = cmid * 4
        for i in range(blocks):
            stride = 2 if (i == 0 and stage > 0) else 1
            a = conv_bn(h, 1, 1, True)
            b = conv_bn(a, 3, stride, True)
            c = conv_bn(b, 1, 1, False)
            sc = (conv_bn(h, 1, stride, False)
                  if (cin != cout or stride != 1) else h)
            h = jnp.maximum(c + sc, 0.0)
            cin = cout
    pooled = h.mean(axis=(2, 3))
    return (jnp.dot(pooled, weights["fc.w"], precision=precision)
            + weights["fc.b"])


def logits(weights, images, stages, precision="highest"):
    """Reference logits as a numpy array; one jitted call."""
    import numpy as np
    dev = {k: jnp.asarray(v) for k, v in weights.items()}
    out = jax.jit(lambda w, x: forward(w, x, stages, PRECISIONS[precision]))(
        dev, jnp.asarray(images, jnp.float32))
    return np.asarray(out)
