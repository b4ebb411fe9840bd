"""The ``resnet`` tier-model family: a 3x3 stem, basic blocks of two 3x3
convolutions with GroupNorm (a 1x1 projection where the shape changes),
stages doubling ``width`` and striding by 2 after the first, global average
pooling and a dense class head (``blocks_per_stage``, ``width``)."""
from functools import partial

from bench import reference as ref
from bench.flops import conv_flops, dense_flops


def register(name: str, spec: dict, registry: dict) -> None:
    """The program's own ``init_resnet``/``apply_resnet`` at the file's
    widths."""
    from repro.models import cnn

    bps = tuple(spec["blocks_per_stage"])
    init = partial(lambda key, num_classes=10, image=16, bps=(), width=0:
                   cnn.init_resnet(key, num_classes, blocks_per_stage=bps,
                                   width=width), bps=bps, width=spec["width"])
    registry[name] = (init, partial(cnn.apply_resnet, blocks_per_stage=bps))


def reference(spec: dict, num_classes: int, image: int):
    """(init(key), apply(params, x)) of the plain float32 reference."""
    bps = tuple(spec["blocks_per_stage"])
    return (partial(ref.init_resnet, num_classes=num_classes, blocks_per_stage=bps,
                    width=spec["width"]),
            partial(ref.apply_resnet, blocks_per_stage=bps))


def forward_flops(spec: dict, image: int, num_classes: int) -> int:
    """One image through the model (``bench/flops.py`` counts)."""
    width = spec["width"]
    h = image
    total = conv_flops(h, 3, 3, width)
    cin = width
    for stage, n in enumerate(spec["blocks_per_stage"]):
        cout = width * 2 ** stage
        for b in range(n):
            stride = 2 if (b == 0 and stage > 0) else 1
            total += conv_flops(h, 3, cin, cout, stride)
            if stride != 1 or cin != cout:
                total += conv_flops(h, 1, cin, cout, stride)
            h = -(-h // stride)
            total += conv_flops(h, 3, cout, cout)
            cin = cout
    return total + dense_flops(cin, num_classes)


def param_count(spec: dict, image: int, num_classes: int) -> int:
    """Parameters of the model: bias-free convolutions, a scale and a bias
    per channel of each GroupNorm, a dense head with bias."""
    width = spec["width"]
    total = 9 * 3 * width
    cin = width
    for stage, n in enumerate(spec["blocks_per_stage"]):
        cout = width * 2 ** stage
        for b in range(n):
            total += 9 * cin * cout + 9 * cout * cout + 4 * cout
            if (b == 0 and stage > 0) or cin != cout:
                total += cin * cout
            cin = cout
    return total + cin * num_classes + num_classes
