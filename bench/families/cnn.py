"""The ``cnn`` tier-model family: three 3x3 convolutions, each with ReLU and
a 2x2 max pool, then a dense class head (``widths``: the three channel
counts)."""
from functools import partial

from bench import reference as ref
from bench.flops import conv_flops, dense_flops


def register(name: str, spec: dict, registry: dict) -> None:
    """The program's own ``init_cnn``/``apply_cnn`` at the file's widths."""
    from repro.models import cnn

    init = partial(lambda key, num_classes=10, image=16, widths=(): cnn.init_cnn(
        key, num_classes, widths=widths, image=image), widths=tuple(spec["widths"]))
    registry[name] = (init, cnn.apply_cnn)


def reference(spec: dict, num_classes: int, image: int):
    """(init(key), apply(params, x)) of the plain float32 reference."""
    return (partial(ref.init_cnn, num_classes=num_classes, image=image,
                    widths=tuple(spec["widths"])), ref.apply_cnn)


def forward_flops(spec: dict, image: int, num_classes: int) -> int:
    """One image through the model (``bench/flops.py`` counts)."""
    c1, c2, c3 = spec["widths"]
    h = image
    total = 0
    for cin, cout in ((3, c1), (c1, c2), (c2, c3)):
        total += conv_flops(h, 3, cin, cout)
        h //= 2
    return total + dense_flops(h * h * c3, num_classes)


def param_count(spec: dict, image: int, num_classes: int) -> int:
    """Parameters of the model: bias-free convolutions, a dense head with
    bias."""
    c1, c2, c3 = spec["widths"]
    feat = (image // 8) ** 2 * c3
    return 9 * (3 * c1 + c1 * c2 + c2 * c3) + feat * num_classes + num_classes
