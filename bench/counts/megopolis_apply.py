"""Compulsory work of one fused Megopolis ``apply`` (Alg. 5 with the state
copy), from the shapes alone, whatever implements it.

Bytes: the weights and the state are read once, the state is written once
(the filter consumes the resampled state; the ancestors it drops are not
counted). Operations: every candidate evaluation (N x B) costs
``OPS_PER_EVAL`` vector operations: the counter hash (two murmur3
finalisers of 8 ops each, the seed and lane mixing, 3 ops; the shift,
convert and scale to a uniform, 3 ops: 22), the comparison index (4), the
accept test ``u * w[k] <= w[j]`` (2) and the selects of the ancestor, its
weight and each state word (2 + state_dim).
"""

HASH_OPS = 22
INDEX_OPS = 4
ACCEPT_OPS = 2


def ops_per_eval(state_dim):
    return HASH_OPS + INDEX_OPS + ACCEPT_OPS + 2 + state_dim


def count(cfg):
    n, b, d = cfg["num_particles"], cfg["num_iters"], cfg["state_dim"]
    word = 4  # float32 weights and state
    return {"bytes": n * word + 2 * n * d * word, "ops": n * b * ops_per_eval(d)}
