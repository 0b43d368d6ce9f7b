"""The benchmark's one generator: the graph, the data and the weights.

Everything a run feeds the system is made here from ``--seed`` and the
parameters of the cell's configuration and traffic files.  Nothing here
imports the program under test.

  - ``gossip_edges``: the §4.2 gossip topology (out-degree ~ U{6, 7});
  - ``image_data`` / ``cnn_init``: CIFAR-10-geometry class-template images
    and the §4.2 CNN's initial weights at the configuration's widths, made
    on the device in one jitted call each.
"""

from __future__ import annotations

import numpy as np


def int_seed(seed: int) -> int:
    """A 31-bit seed for JAX's PRNG drawn from any whole-number seed."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


# -- gossip FL: graph, data, weights -------------------------------------------

def random_edges(rng: np.random.Generator, num_tasks: int, lo: int, hi: int):
    """Each task sends to ``U{lo, hi}`` distinct other tasks; sorted, unique."""
    hi = min(hi, num_tasks - 1)
    lo = min(lo, hi)
    edges = []
    for i in range(num_tasks):
        deg = int(rng.integers(lo, hi + 1))
        others = [j for j in range(num_tasks) if j != i]
        edges.extend((i, int(t)) for t in rng.choice(others, size=deg, replace=False))
    return tuple(sorted(set(edges)))


def gossip_edges(seed: int, users: int, lo: int, hi: int):
    return random_edges(np.random.default_rng([seed, 2]), users, lo, hi)


def _barcode(classes: int, h: int, w: int) -> np.ndarray:
    """Class i lights coarse cell i of a 2x5 grid: separable with margin."""
    grid_h, grid_w = 2, 5
    ch, cw = h // grid_h, w // grid_w
    mask = np.zeros((classes, h, w, 1), np.float32)
    for i in range(classes):
        r, col = divmod(i % (grid_h * grid_w), grid_w)
        mask[i, r * ch:(r + 1) * ch, col * cw:(col + 1) * cw] = 1.0
    return mask


def image_data(seed: int, users: int, per_user: int, image, classes: int,
               noise: float):
    """(x (users, per_user, h, w, c) in [0, 1], y (users, per_user) int32),
    made on the device: smooth random class templates (a 4x4 field per
    class, bilinearly upsampled) half-weighted under a class barcode, plus
    Gaussian noise."""
    import jax
    import jax.numpy as jnp

    h, w, c = image
    mask = jnp.asarray(_barcode(classes, h, w))

    @jax.jit
    def make(key):
        kf, ky, kn = jax.random.split(key, 3)
        field = jax.random.normal(kf, (classes, 4, 4, c))
        tmpl = jax.image.resize(field, (classes, h, w, c), "linear")
        tmpl = (tmpl - tmpl.min()) / (tmpl.max() - tmpl.min())
        tmpl = 0.5 * tmpl + 0.5 * mask
        y = jax.random.randint(ky, (users, per_user), 0, classes, jnp.int32)
        x = tmpl[y] + noise * jax.random.normal(kn, (users, per_user, h, w, c))
        return jnp.clip(x, 0.0, 1.0), y

    return make(jax.random.PRNGKey(int_seed(seed)))


def cnn_init(seed: int, image, classes: int, channels=(32, 64), hidden=(128, 64)):
    """He-normal weights and zero biases of the §4.2 CNN, as a dict of
    ``conv1, conv2`` (3x3, HWIO, ``channels`` out) and ``fc1, fc2, fc3``
    (in, out; ``hidden`` wide, then ``classes``) layers."""
    import jax
    import jax.numpy as jnp

    h, w, c = image
    c1, c2 = channels
    h1, h2 = hidden
    flat = (h // 4) * (w // 4) * c2
    shapes = {
        "conv1": (3, 3, c, c1), "conv2": (3, 3, c1, c2),
        "fc1": (flat, h1), "fc2": (h1, h2), "fc3": (h2, classes),
    }

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, shp) in zip(keys, shapes.items()):
            fan_in = int(np.prod(shp[:-1]))
            out[name] = {"w": jax.random.normal(k, shp) * np.sqrt(2.0 / fan_in),
                         "b": jnp.zeros(shp[-1])}
        return out

    return make(jax.random.PRNGKey(int_seed([seed, 3])))
