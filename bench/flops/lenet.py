"""FLOPs LeNet-5 requires, from its shapes.

Counted: the multiply-adds (2 FLOPs each) of the two 5x5 VALID
convolutions and the three dense layers.  Forward: each layer once.
Backward: the weight gradient of every layer, and the input gradient of
every layer but the first (the images need none).  Bias adds, ReLUs,
pooling and the softmax are left out: they are elementwise, under 2% of
the total.  An MNIST sample (28x28x1) needs 563,280 forward FLOPs and
1,517,040 forward+backward; a CIFAR sample (32x32x3) 1,303,440 and
3,204,720.
"""
from __future__ import annotations


def layers(img: int, ch: int, classes: int = 10):
    """Per-sample forward FLOPs of each layer, in order."""
    o1 = img - 4                       # conv1 output side
    s1 = o1 // 2
    o2 = s1 - 4
    s2 = o2 // 2
    return [
        2 * o1 * o1 * 6 * (25 * ch),       # conv1
        2 * o2 * o2 * 16 * (25 * 6),       # conv2
        2 * (16 * s2 * s2) * 120,          # f1
        2 * 120 * 84,                      # f2
        2 * 84 * classes,                  # f3
    ]


def forward(img: int, ch: int, classes: int = 10) -> int:
    return sum(layers(img, ch, classes))


def train_step(img: int, ch: int, classes: int = 10) -> int:
    """Forward + backward FLOPs of one training sample."""
    per = layers(img, ch, classes)
    return 3 * sum(per) - per[0]


def per_unit(shape: dict, traffic: dict) -> int:
    """FLOPs one round (sync) or one event (async) requires, on average
    over a call: every training client's local SGD steps plus the
    evaluation forward passes of the call's eval rounds.  The MAML re-cluster is left out, so this is a lower bound."""
    img, ch, classes = shape["img"], shape["ch"], shape["classes"]
    clients = traffic.get("async", {}).get("cohort") or shape["clients"]
    train = (clients * shape["local_steps"] * shape["batch_size"]
             * train_step(img, ch, classes))
    rounds, every = traffic["rounds"], traffic["eval_every"]
    evals = sum(1 for r in range(1, rounds + 1)
                if r % every == 0 or r == rounds)
    return int(train + shape["eval_size"] * forward(img, ch, classes)
               * evals / rounds)
