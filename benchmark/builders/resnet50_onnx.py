"""ResNet-50 as an ONNX payload, weights and images from the seed.

The graph builder is ``bench_onnx._resnet50_proto`` ([3,4,6,3]
bottlenecks, 25.5M float32 parameters, stride in the 3x3 convolution),
copied here so that a later PR to that script cannot move the
yardstick. Departures: the weights are made first, as an ordered dict
of arrays that the plain reference (``reference/resnet50.py``) is
given too; the batch-norm statistics are seeded and not the identity,
so that a fault in the importer's batch-norm cannot hide; the stage
list and the image side come from the configuration (the rehearsal and
the tests run a toy).
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def make_weights(seed, stages, stem=64, classes=1000):
    """``{name: float32 array}`` in graph order. A convolution ``c<i>``
    comes with its batch-norm ``c<i>.scale/.bias/.mean/.var``."""
    rng = np.random.default_rng(seed)
    weights = {}

    def conv(cin, cout, k):
        name = f"c{len([n for n in weights if n.endswith('.w')])}"
        weights[name + ".w"] = (
            rng.standard_normal(size=(cout, cin, k, k), dtype=np.float32)
            * np.float32((2.0 / (cin * k * k)) ** 0.5))
        weights[name + ".scale"] = rng.uniform(
            0.5, 1.0, size=cout).astype(np.float32)
        weights[name + ".bias"] = (
            rng.standard_normal(size=cout, dtype=np.float32) * 0.1)
        weights[name + ".mean"] = (
            rng.standard_normal(size=cout, dtype=np.float32) * 0.1)
        weights[name + ".var"] = rng.uniform(
            0.5, 1.5, size=cout).astype(np.float32)

    conv(3, stem, 7)
    cin = stem
    for stage, (blocks, cmid) in enumerate(stages):
        cout = cmid * 4
        for i in range(blocks):
            stride = 2 if (i == 0 and stage > 0) else 1
            conv(cin, cmid, 1)
            conv(cmid, cmid, 3)
            conv(cmid, cout, 1)
            if cin != cout or stride != 1:
                conv(cin, cout, 1)
            cin = cout
    weights["fc.w"] = (rng.standard_normal(size=(cin, classes),
                                           dtype=np.float32) * 0.01)
    weights["fc.b"] = (rng.standard_normal(size=classes,
                                           dtype=np.float32) * 0.01)
    return weights


def make_proto(weights, stages, image, epsilon=1e-5):
    from mmlspark_tpu.onnx import onnx_subset_pb2 as pb

    model = pb.ModelProto()
    g = model.graph
    g.name = "resnet50"

    def tensor(name):
        arr = weights[name]
        t = g.initializer.add()
        t.name = name
        t.data_type = 1
        t.dims.extend(list(arr.shape))
        t.raw_data = np.ascontiguousarray(arr, np.float32).tobytes()
        return name

    def node(op, inputs, outputs, **attrs):
        nd = g.node.add()
        nd.op_type = op
        nd.input.extend(inputs)
        nd.output.extend(outputs)
        for k, v in attrs.items():
            a = nd.attribute.add()
            a.name = k
            if isinstance(v, int):
                a.i = v
                a.type = 2
            elif isinstance(v, float):
                a.f = v
                a.type = 1
            elif isinstance(v, (list, tuple)):
                a.ints.extend(v)
                a.type = 7

    uid = [0]
    convs = [0]

    def nm(prefix):
        uid[0] += 1
        return f"{prefix}{uid[0]}"

    def conv_bn_relu(x, k, stride, relu=True):
        name = f"c{convs[0]}"
        convs[0] += 1
        y = nm("conv")
        pad = k // 2
        node("Conv", [x, tensor(name + ".w")], [y],
             strides=[stride, stride], pads=[pad, pad, pad, pad],
             kernel_shape=[k, k])
        z = nm("bn")
        node("BatchNormalization",
             [y] + [tensor(f"{name}.{p}")
                    for p in ("scale", "bias", "mean", "var")],
             [z], epsilon=epsilon)
        if not relu:
            return z
        r = nm("relu")
        node("Relu", [z], [r])
        return r

    def bottleneck(x, project, stride):
        a = conv_bn_relu(x, 1, 1)
        b = conv_bn_relu(a, 3, stride)
        c = conv_bn_relu(b, 1, 1, relu=False)
        sc = conv_bn_relu(x, 1, stride, relu=False) if project else x
        s = nm("add")
        node("Add", [c, sc], [s])
        r = nm("relu")
        node("Relu", [s], [r])
        return r

    inp = g.input.add()
    inp.name = "x"
    inp.type.tensor_type.elem_type = 1
    for d in (0, 3, image, image):
        dim = inp.type.tensor_type.shape.dim.add()
        dim.dim_value = d

    h = conv_bn_relu("x", 7, 2)
    p = nm("pool")
    node("MaxPool", [h], [p], kernel_shape=[3, 3], strides=[2, 2],
         pads=[1, 1, 1, 1])
    h = p
    cin = weights["c0.w"].shape[0]
    for stage, (blocks, cmid) in enumerate(stages):
        cout = cmid * 4
        for i in range(blocks):
            stride = 2 if (i == 0 and stage > 0) else 1
            h = bottleneck(h, cin != cout or stride != 1, stride)
            cin = cout
    gap = nm("gap")
    node("GlobalAveragePool", [h], [gap])
    flat = nm("flat")
    node("Flatten", [gap], [flat], axis=1)
    node("Gemm", [flat, tensor("fc.w"), tensor("fc.b")], ["logits"])
    out = g.output.add()
    out.name = "logits"
    out.type.tensor_type.elem_type = 1
    return model.SerializeToString()


def make_frames(seed, frames, rows, image, threads=8):
    """``frames`` object columns of ``rows`` ``(3, image, image)``
    float32 arrays each, as the image stages produce them. Each frame
    comes from its own child of the seed, so threads change nothing."""
    children = np.random.SeedSequence([seed, 1]).spawn(frames)

    def make(child):
        block = np.random.default_rng(child).standard_normal(
            size=(rows, 3, image, image), dtype=np.float32)
        col = np.empty(rows, dtype=object)
        for i in range(rows):
            col[i] = block[i]
        return col

    with ThreadPoolExecutor(max_workers=threads) as pool:
        return list(pool.map(make, children))


def build(ctx):
    from mmlspark_tpu.onnx.model import ONNXModel

    cfg = ctx.config
    stages = [tuple(s) for s in cfg["stages"]]
    weights = make_weights(ctx.seed, stages, cfg["stem_width"],
                           cfg["classes"])
    payload = make_proto(weights, stages, cfg["image_side"])
    model = ONNXModel(modelPayload=payload,
                      miniBatchSize=cfg["miniBatchSize"])
    return {"weights": weights, "stages": stages, "model": model,
            "parameters": int(sum(w.size for n, w in weights.items()
                                  if n.endswith(".w") or n == "fc.b"))}
