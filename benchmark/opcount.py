"""Operations and bytes an algorithm needs, computed from shapes.

These are the yardstick's counts: what the mathematics requires, not
what an implementation happens to execute. Padding (255 -> 256 bins,
3 -> 8 stats rows), multi-pass float32 products on the MXU, sorts and
gathers that a kernel adds around the mathematics are the
implementation's and are NOT counted, so a roofline share computed
from these cannot be raised by doing more work.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip; an unknown kind is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(have {sorted(table)}); add a line with its source")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float,
                  peak: Dict[str, float]) -> Tuple[float, str]:
    """Roofline floor: the larger of operations over the bf16 peak rate
    and bytes over peak bandwidth, and which of the two it is."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))


def hist_level(rows: int, features: int, bins: int) -> Tuple[float, float]:
    """One level of a histogram GBDT as a one-hot matmul (ROADMAP S1).

    FLOPs: every (row, feature) pair contributes its three statistics
    (gradient, hessian, count) to one of ``bins`` bins; as a matmul
    against the one-hot that is one multiply and one add per bin and
    statistic: rows * features * bins * 2 * 3.
    Bytes: the binned matrix once (one byte a cell) plus 16 bytes a row
    of gradient, hessian, live flag and node id (float32/int32).
    At 2,000,000 x 28 x 255 that is 8.6e10 FLOP and 88 MB.
    """
    flops = float(rows) * features * bins * 2 * 3
    nbytes = float(rows) * features + float(rows) * 16
    return flops, nbytes


# ResNet-50 v1 (He et al. 2015, table 1, 50-layer column): stage widths
# and block counts. Strides sit on the 3x3 convolution of a stage's
# first block (the v1.5 placement ``builders/resnet50_onnx.py`` and
# torchvision use); the count below follows the graph that is run.
RESNET50_STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))


def resnet50_conv_shapes(image: int = 224, stages=RESNET50_STAGES,
                         stem: int = 64, classes: int = 1000,
                         stride_on_3x3: bool = True
                         ) -> List[Tuple[int, int, int, int]]:
    """``(cin, cout, kernel, out_side)`` of every convolution, then the
    classifier as a 1x1 convolution on a 1x1 map. ``stride_on_3x3``
    False puts a stage's stride on its first 1x1 convolution, as the
    paper's table 1 counts it."""
    shapes = []
    side = image // 2                       # 7x7 stride 2
    shapes.append((3, stem, 7, side))
    side //= 2                              # 3x3 max-pool stride 2
    cin = stem
    for stage, (blocks, cmid) in enumerate(stages):
        cout = cmid * 4
        for i in range(blocks):
            stride = 2 if (i == 0 and stage > 0) else 1
            side_out = side // stride
            shapes.append((cin, cmid, 1,                   # 1x1 reduce
                           side if stride_on_3x3 else side_out))
            shapes.append((cmid, cmid, 3, side_out))       # 3x3 (strided)
            shapes.append((cmid, cout, 1, side_out))       # 1x1 expand
            if cin != cout or stride != 1:
                shapes.append((cin, cout, 1, side_out))    # projection
            side, cin = side_out, cout
    shapes.append((cin, classes, 1, 1))
    return shapes


def resnet50_macs(image: int = 224, **kw) -> float:
    """Multiply-adds of one image's forward pass through the
    convolutions and the classifier (batch-norm, ReLU, pooling and the
    residual adds are not counted, as in the published figure).
    The paper's table gives 3.8e9 for its placement of the strides
    (``stride_on_3x3=False``: 3.86e9 here); the graph that is run
    strides in the 3x3 and needs 4.09e9."""
    return float(sum(cin * cout * k * k * side * side
                     for cin, cout, k, side in
                     resnet50_conv_shapes(image, **kw)))


def resnet50_flops(images: int, image: int = 224, **kw) -> float:
    return 2.0 * resnet50_macs(image, **kw) * images
