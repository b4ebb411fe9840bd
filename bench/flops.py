"""Operations of the tier models, worked out from their shapes.

Counts are of multiply-adds times two in the convolutions and dense layers,
per image, for a forward pass. A convolution counts the kernel taps that
fall inside the image: multiplications by ``SAME`` padding's zeros are not
operations the model needs. Normalisations, activations, pooling and the
losses are left out: they are a fraction of a percent of these models'
work. A training step counts three forward passes (the forward pass and a
backward pass of twice its cost); nothing is counted twice for
recomputation. Each model family counts its own forward pass from these
primitives (``bench/families/``).
"""
from __future__ import annotations

from bench.spec import family


def taps(n_in: int, k: int, stride: int) -> int:
    """Kernel taps inside the input, summed over the outputs of one axis of
    a ``SAME``-padded convolution."""
    n_out = -(-n_in // stride)
    pad_lo = max((n_out - 1) * stride + k - n_in, 0) // 2
    return sum(min(k, n_in + pad_lo - o * stride) - max(0, pad_lo - o * stride)
               for o in range(n_out))


def conv_flops(h_in: int, k: int, cin: int, cout: int, stride: int = 1) -> int:
    """A square ``SAME``-padded convolution of one image."""
    return 2 * taps(h_in, k, stride) ** 2 * cin * cout


def dense_flops(din: int, dout: int) -> int:
    return 2 * din * dout


def forward_flops(spec: dict, image: int, num_classes: int) -> int:
    """One image through one entry of a configuration's ``models``, as its
    family (``bench/families/<family>.py``) counts it."""
    return family(spec["family"]).forward_flops(spec, image, num_classes)


def decode_flops(image: int, embed_dim: int, width: int = 16) -> int:
    """One bridge sample through the decoder."""
    s = image // 4
    return (dense_flops(embed_dim, s * s * width)
            + conv_flops(2 * s, 3, width, width)
            + conv_flops(4 * s, 3, width, 3))


def direction_flops(config: dict, steps: int, bs: int, teacher: str, student: str,
                    local_bs: int) -> int:
    """One BSBODP direction: per step, the decode of the bridge batch, the
    teacher's forward pass, and the student's training step on the bridge
    batch and, for a data-holding student, on its local batch."""
    fl, models = config["fl_config"], config["models"]
    img, nc = fl["image_size"], fl["num_classes"]
    per_step = (bs * decode_flops(img, fl["embed_dim"])
                + bs * forward_flops(models[teacher], img, nc)
                + 3 * (bs + local_bs) * forward_flops(models[student], img, nc))
    return steps * per_step
