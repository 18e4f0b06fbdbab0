"""Compulsory work of one whole UNGM filter step (predict, weight,
resample, estimate), from the shapes alone.

Bytes: the carried state is read and written once; in conditional SIR
(``ess_threshold`` set) the carried log-weights are too. Everything else a
step touches can live on chip. Operations per particle: the transition
(``MODEL_OPS``: x/2, x^2, 1 + x^2, 25x, the division, three adds, the
noise draw's scale: 9), the normal draw (``NOISE_OPS``: a counter-based
draw and its inverse-CDF transform, 20), the likelihood (``LIKELIHOOD_OPS``:
x^2/20, the residual, its square, two scalings and exp: 6), the estimate
(``ESTIMATE_OPS``: the mean, 1; in SIR ``SIR_OPS``: the log-weight update,
floor, log and add, 3, and the weighted mean, 3), and the resampling
kernel's operations from its own ``counts/`` file.
"""

import registry

MODEL_OPS = 9
NOISE_OPS = 20
LIKELIHOOD_OPS = 6
ESTIMATE_OPS = 1
SIR_OPS = 6


def count(cfg):
    n, d = cfg["num_particles"], cfg["state_dim"]
    word = 4
    sir = cfg["ess_threshold"] is not None
    kernel = registry.work(cfg, "kernel")
    per_particle = MODEL_OPS + NOISE_OPS + LIKELIHOOD_OPS + (SIR_OPS if sir else ESTIMATE_OPS)
    carried = n * d * word + (n * word if sir else 0)
    return {"bytes": 2 * carried, "ops": n * per_particle + kernel["ops"]}
