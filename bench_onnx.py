"""Benchmark: ResNet-50-shaped ONNX scoring through the XLA importer.

BASELINE.md's second north star is ONNXModel ResNet-50 scoring at >=
GPU-executor throughput. Zero-egress, so the graph is constructed
in-memory with the standard ResNet-50 topology ([3,4,6,3] bottlenecks,
25.5M params) and random weights — identical compute/memory profile to
the real checkpoint, which is what throughput measures.

Prints ONE JSON line: {"metric", "value", "unit", "batch"}.
Run: python bench_onnx.py [batch] (JAX_PLATFORMS=cpu for a CPU run)
"""

import json
import sys
import time

import numpy as np


def _resnet50_proto(rng):
    from mmlspark_tpu.onnx import onnx_subset_pb2 as pb

    model = pb.ModelProto()
    g = model.graph
    g.name = "resnet50"

    def tensor(name, arr):
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

    def nm(prefix):
        uid[0] += 1
        return f"{prefix}{uid[0]}"

    def conv_bn_relu(x, cin, cout, k, stride, relu=True):
        w = tensor(nm("w"), rng.normal(size=(cout, cin, k, k)).astype(
            np.float32) * (2.0 / (cin * k * k)) ** 0.5)
        y = nm("conv")
        pad = k // 2
        node("Conv", [x, w], [y], strides=[stride, stride],
             pads=[pad, pad, pad, pad], kernel_shape=[k, k])
        scale = tensor(nm("s"), np.ones(cout, np.float32))
        bias = tensor(nm("b"), np.zeros(cout, np.float32))
        mean = tensor(nm("m"), np.zeros(cout, np.float32))
        var = tensor(nm("v"), np.ones(cout, np.float32))
        z = nm("bn")
        node("BatchNormalization", [y, scale, bias, mean, var], [z],
             epsilon=1e-5)
        if not relu:
            return z
        r = nm("relu")
        node("Relu", [z], [r])
        return r

    def bottleneck(x, cin, cmid, cout, stride):
        a = conv_bn_relu(x, cin, cmid, 1, 1)
        b = conv_bn_relu(a, cmid, cmid, 3, stride)
        c = conv_bn_relu(b, cmid, cout, 1, 1, relu=False)
        if cin != cout or stride != 1:
            sc = conv_bn_relu(x, cin, cout, 1, stride, relu=False)
        else:
            sc = x
        s = nm("add")
        node("Add", [c, sc], [s])
        r = nm("relu")
        node("Relu", [s], [r])
        return r

    inp = g.input.add()
    inp.name = "x"
    inp.type.tensor_type.elem_type = 1
    for d in (0, 3, 224, 224):
        dim = inp.type.tensor_type.shape.dim.add()
        dim.dim_value = d

    h = conv_bn_relu("x", 3, 64, 7, 2)
    p = nm("pool")
    node("MaxPool", [h], [p], kernel_shape=[3, 3], strides=[2, 2],
         pads=[1, 1, 1, 1])
    h = p
    cin = 64
    for stage, (blocks, cmid) in enumerate(
            [(3, 64), (4, 128), (6, 256), (3, 512)]):
        cout = cmid * 4
        for i in range(blocks):
            stride = 2 if (i == 0 and stage > 0) else 1
            h = bottleneck(h, cin, cmid, cout, stride)
            cin = cout
    gap = nm("gap")
    node("GlobalAveragePool", [h], [gap])
    flat = nm("flat")
    node("Flatten", [gap], [flat], axis=1)
    wfc = tensor("w_fc", rng.normal(size=(2048, 1000)).astype(np.float32)
                 * 0.01)
    bfc = tensor("b_fc", np.zeros(1000, np.float32))
    node("Gemm", [flat, wfc, bfc], ["logits"])
    out = g.output.add()
    out.name = "logits"
    out.type.tensor_type.elem_type = 1
    return model.SerializeToString()


def main():
    from bench import device_stamp
    stamp = device_stamp()
    import jax
    from mmlspark_tpu.core.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.onnx.model import ONNXModel

    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    batch = int(args[0]) if args else 64
    rng = np.random.default_rng(0)
    payload = _resnet50_proto(rng)

    imgs = np.empty(batch, dtype=object)
    for i in range(batch):
        imgs[i] = rng.normal(size=(3, 224, 224)).astype(np.float32)
    df = DataFrame({"features": imgs})
    m = ONNXModel(modelPayload=payload, miniBatchSize=batch)
    m.transform(df)  # compile

    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        out = m.transform(df)
    dt = (time.perf_counter() - t0) / reps
    print(json.dumps({
        "metric": "onnx_resnet50_scoring",
        "value": round(batch / dt, 1),
        "unit": "images/s",
        "batch": batch,
        "backend": jax.default_backend(),
        **stamp,
    }))


if __name__ == "__main__":
    main()
