"""Operations and bytes of the measured work, counted from shapes.

These are what the algorithm needs, not what a kernel happens to move:
padding to a block multiple is not counted, so a share of the roofline
can only be understated by them.  A multiply-add counts as two
operations; every array is float32 (4 bytes a value) unless stated.
"""

from __future__ import annotations

F32 = 4


# -- the paper's CNN (§4.2): two 3x3 SAME convs with 2x2 max-pools, then
#    three dense layers ------------------------------------------------------

def cnn_layer_macs(image: tuple[int, int, int], classes: int = 10,
                   channels: tuple[int, int] = (32, 64),
                   hidden: tuple[int, int] = (128, 64)) -> list[int]:
    """Multiply-adds per sample of each weight layer, forward."""
    h, w, c = image
    c1, c2 = channels
    conv1 = h * w * 3 * 3 * c * c1
    h2, w2 = h // 2, w // 2
    conv2 = h2 * w2 * 3 * 3 * c1 * c2
    flat = (h2 // 2) * (w2 // 2) * c2
    return [conv1, conv2, flat * hidden[0], hidden[0] * hidden[1],
            hidden[1] * classes]


def cnn_forward_flops(image, classes: int = 10, channels=(32, 64), hidden=(128, 64)) -> int:
    return 2 * sum(cnn_layer_macs(image, classes, channels, hidden))


def cnn_train_flops(image, classes: int = 10, channels=(32, 64), hidden=(128, 64)) -> int:
    """Forward plus backward per sample: the forward, the weight gradients
    of every layer (as many operations again), and the input gradients of
    every layer but the first (nothing upstream of the image needs one).
    Bias adds, activations, pooling and the loss are not counted."""
    macs = cnn_layer_macs(image, classes, channels, hidden)
    return 2 * sum(macs) + 2 * sum(macs) + 2 * sum(macs[1:])


def cnn_param_count(image, classes: int = 10,
                    channels: tuple[int, int] = (32, 64),
                    hidden: tuple[int, int] = (128, 64)) -> int:
    h, w, c = image
    c1, c2 = channels
    flat = (h // 4) * (w // 4) * c2
    return (9 * c * c1 + c1 + 9 * c1 * c2 + c2 + flat * hidden[0] + hidden[0]
            + hidden[0] * hidden[1] + hidden[1] + hidden[1] * classes + classes)


# -- gossip mix: out (N, L) = W (N, N) @ X (N, L) -----------------------------

def mix_cost(users: int, length: int) -> tuple[int, int]:
    """(operations, bytes) of one all-receivers mix of ``length`` values per
    user: X read once, W read once, the mixed rows written once."""
    ops = 2 * users * users * length
    nbytes = F32 * (2 * users * length + users * users)
    return ops, nbytes


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bytes: float) -> tuple[float, str]:
    """Least time the chip could take over the time taken, in percent, and
    which bound sets that least time ("compute" or "memory")."""
    t_ops, t_mem = ops / peak_flops, nbytes / peak_bytes
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
