"""Named scopes over the program's work (DESIGN.md §15).

``span(name)`` is ``jax.named_scope(name)``: the ops traced inside it carry
``name`` in their name stack, which lowering writes into each HLO op's
``metadata={op_name=...}`` and the profiler reports per device op as its
``tf_op``.  A scope is metadata only: the jaxpr, and the compiled program
once metadata is stripped, are those of the unscoped code, and the
persistent compilation cache's key leaves metadata out.  So spans are
always on, with no switch.

Three kinds are opened:

* the filter's stages, ``pf/predict``, ``pf/update``, ``pf/resample`` and
  ``pf/estimate`` (``pf/filter.py``);
* one per ``Resampler`` public entry, ``dispatch_span``::

      family/backend/entry/plane_dtype     e.g. megopolis/pallas/step/bfloat16

* ``resample/planes`` around the kernels' state-plane pack and unpack
  (``kernels/common.py``).
"""

from __future__ import annotations

import jax


def span(name: str):
    """A named scope around a region; costs nothing in the compiled program."""
    return jax.named_scope(name)


def dispatch_span(family: str, backend: str, entry: str, plane_dtype="float32"):
    """The canonical dispatch span: ``family/backend/entry/plane_dtype``."""
    return span(f"{family}/{backend}/{entry}/{plane_dtype}")
