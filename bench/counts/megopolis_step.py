"""Compulsory work of one fused Megopolis ``step`` (normalise, ESS, the
conditional resample and the state copy), from the shapes alone.

Bytes: the log-weights and the state are read once, the state and the four
stats words are written once. Operations: the normalisation and the
statistics cost ``PRELUDE_OPS`` per particle (max, subtract, exp, the two
sums, the square, the max weight), and the sweeps cost what ``apply``'s
do: the step always sweeps, and commits or discards what it found.
"""

import registry

PRELUDE_OPS = 7


def count(cfg):
    n, b, d = cfg["num_particles"], cfg["num_iters"], cfg["state_dim"]
    word = 4
    return {"bytes": n * word + 2 * n * d * word + 4 * word,
            "ops": n * PRELUDE_OPS + n * b * registry.load_module(
                "counts", "megopolis_apply").ops_per_eval(d)}
