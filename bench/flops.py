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

The least bytes a call must move through HBM are counted from parameters
alone, in float32: a forward pass reads its model's parameters once, and a
student step moves ``STUDENT_ARRAYS`` parameter-sized arrays (the
parameters and Adam's ``m`` and ``v``, each read and written, and either
the gradient written and read or the parameters read again by the backward
pass). Activations, inputs and outputs are left out, so the bytes are a
floor. The decoder's bytes count 0: a coalesced group of pairs decodes in
one call, which reads the decoder once, and the round's log does not say
which pairs shared a call (the decoder holds 0.07 M parameters).
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


def param_count(spec: dict, image: int, num_classes: int) -> int:
    """Parameters of one entry of a configuration's ``models``, as its
    family counts them."""
    return family(spec["family"]).param_count(spec, image, num_classes)


def decode_flops(image: int, embed_dim: int, width: int = 16) -> int:
    """One bridge sample through the decoder."""
    s = image // 4
    return (dense_flops(embed_dim, s * s * width)
            + conv_flops(2 * s, 3, width, width)
            + conv_flops(4 * s, 3, width, 3))


F32 = 4
STUDENT_ARRAYS = 8
DECODER = "decoder"


def direction_work(config: dict, steps: int, bs: int, teacher: str, student: str,
                   local_bs: int) -> dict[tuple[str, str], tuple[int, int]]:
    """One BSBODP direction, (model, step) -> (operations, least HBM bytes):
    per step, the decode of the bridge batch (``decode``), the teacher's
    forward pass (``teacher``), and the student's training step on the
    bridge batch and, for a data-holding student (``local_bs`` rows of its
    own), on its local batch (``student_leaf``; ``student`` otherwise), each
    step the compiled program of that name."""
    fl, models = config["fl_config"], config["models"]
    img, nc = fl["image_size"], fl["num_classes"]
    t, s = models[teacher], models[student]
    return {
        (DECODER, "decode"): (steps * bs * decode_flops(img, fl["embed_dim"]), 0),
        (teacher, "teacher"): (steps * bs * forward_flops(t, img, nc),
                               steps * F32 * param_count(t, img, nc)),
        (student, "student_leaf" if local_bs else "student"): (
            steps * 3 * (bs + local_bs) * forward_flops(s, img, nc),
            steps * STUDENT_ARRAYS * F32 * param_count(s, img, nc)),
    }


def eval_work(config: dict, batch: int) -> tuple[int, int]:
    """The cloud model's forward pass over the test split in calls of
    ``batch`` images: (operations, least HBM bytes)."""
    fl = config["fl_config"]
    cloud, img, nc, n = (config["models"][fl["cloud_model"]], fl["image_size"],
                         fl["num_classes"], fl["test_samples"])
    return n * forward_flops(cloud, img, nc), -(-n // batch) * F32 * param_count(cloud, img, nc)


def add_work(work: dict, key: tuple[str, str], flops: int, nbytes: int) -> None:
    """Add to ``work[model][step]``, the form a ``record`` carries."""
    w = work.setdefault(key[0], {}).setdefault(key[1], {"flops": 0, "bytes": 0})
    w["flops"] += flops
    w["bytes"] += nbytes
