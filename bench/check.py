"""The comparison that decides ``correct``.

After the window has closed, a sample of the tracks it finished, drawn
from the seed, is filtered again by the configuration's plain reference
(``reference/<name>.py``) with the same keys and observations. Every
estimate the window produced for those tracks is compared with the
reference's. Two numbers, each beside its limit from the configuration's
``limits``:

* ``est_gap``: the widest gap ``|est - est_ref|`` over every compared
  estimate, in state units. The estimates depend on the model's transition
  and likelihood, on the kernel's ancestor choice and on its state copy, so
  a fault in any of them shows here;
* ``nonfinite``: estimates of the window that are not finite (limit 0).
"""

from __future__ import annotations

import numpy as np


def sample_tracks(window, traffic, seed):
    """Finished tracks to compare, drawn from the seed."""
    full = sorted(r for r, est in window.tracks.items()
                  if len(est) == traffic["steps_per_track"])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    k = min(traffic["check_tracks"], len(full))
    return sorted(rng.choice(full, size=k, replace=False).tolist()) if k else []


def reference_estimates(config, reference, base_key, pool_zs, tracks, dtype=None):
    """The reference's ``f32[K, T]`` estimates of ``tracks``."""
    import jax
    import jax.numpy as jnp

    keys = jnp.stack([jax.random.fold_in(base_key, np.uint32(r)) for r in tracks])
    zs = jnp.asarray(np.stack([pool_zs[r % len(pool_zs)] for r in tracks]))
    return np.asarray(reference.filter_tracks(config, keys, zs, dtype or jnp.float32))


def compare(config, traffic, window, reference, base_key, pool_zs, seed):
    """``(correct, checks)`` with ``checks = {name: {"value", "limit"}}``."""
    limits = config["limits"]
    tracks = sample_tracks(window, traffic, seed)
    nonfinite = int(sum(int(np.sum(~np.isfinite(e))) for e in window.tracks.values()))
    if tracks:
        got = np.stack([window.tracks[r] for r in tracks])
        want = reference_estimates(config, reference, base_key, pool_zs, tracks)
        gap = float(np.max(np.abs(got.astype(np.float64) - want)))
        if not np.isfinite(gap):
            gap = float("inf")
    else:
        gap = float("inf")  # nothing finished: nothing proven
    checks = {
        "est_gap": {"value": gap, "limit": limits["est_gap"]},
        "nonfinite": {"value": nonfinite, "limit": 0},
        "tracks_compared": {"value": len(tracks), "limit": 1},
    }
    correct = (gap <= limits["est_gap"] and nonfinite == 0 and len(tracks) >= 1)
    return correct, checks
