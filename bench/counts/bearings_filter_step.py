"""Compulsory work of one whole bearings-only filter step (predict, weight,
resample, estimate; Alg. 6), from the shapes alone.

Bytes: the carried state, ``[N, 4]`` f32, is read and written once:
``2 * N * 4 * 4``. Everything else a step touches can live on chip.
Operations per particle: the two normal draws of the process noise
(``NOISE_OPS`` each, a counter-based draw and its inverse-CDF transform:
20), the constant-velocity move (``MOVE_OPS``: two noise scalings, two
halvings, six adds: 10), the likelihood (``LIKELIHOOD_OPS``: atan2, the
residual, the wrap's subtract, remainder and subtract, the square, two
scalings and exp: 9), the estimate (``ESTIMATE_OPS``: one add per
component, 4), and the resampling kernel's operations from its own
``counts/`` file.
"""

import registry

NOISE_OPS = 20
MOVE_OPS = 10
LIKELIHOOD_OPS = 9
ESTIMATE_OPS = 4


def count(cfg):
    n, d = cfg["num_particles"], cfg["state_dim"]
    word = 4
    kernel = registry.work(cfg, "kernel")
    per_particle = 2 * NOISE_OPS + MOVE_OPS + LIKELIHOOD_OPS + ESTIMATE_OPS
    return {"bytes": 2 * n * d * word, "ops": n * per_particle + kernel["ops"]}
