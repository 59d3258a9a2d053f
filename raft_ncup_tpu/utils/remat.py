"""What the training step keeps across its refinement loop, by name.

The loop's body is rematerialised (``models/raft.py``: ``jax.checkpoint``
around the scan's step), so the backward runs every iteration's forward a
second time. Two kinds of value are worth more kept than computed again
(milliseconds recovered per megabyte held; PERF.md section 6, PR 38):

- :data:`LOOKUP_OUT`: the correlation lookup's K*K*L planes at 1/8
  resolution. The coordinates are detached, so the volume's cotangent needs
  the lookup's axis weights alone, and with the planes kept the contraction
  over the pyramid is dead code in the second forward.
- :data:`WEIGHTS_NET_CONV`: each hidden convolution's output of NCUP's
  weights net, before its norm and ReLU (``nn/weights_est.py``).

NCUP's own full-resolution planes are never kept: ~2 GB an iteration at the
Sintel fine-tune's size, for the ~31 ms a step that are left of its second
forward.

The sites mark their values with ``jax.ad_checkpoint.checkpoint_name``,
which lowers to nothing: a program that builds no checkpoint (every
inference program) is the same module with and without the marks.
:func:`save_named` is the ``jax.checkpoint`` policy that keeps them, and
tallies what it kept while its program was traced, in the manner of
``precision/sites.py`` (reset before a program is lowered, read after it,
in one thread: ``inference/costs.build_and_record``).
"""

from __future__ import annotations

import jax

LOOKUP_OUT = "raft.corr_lookup.out"
WEIGHTS_NET_CONV = "ncup.weights_net.conv"
SAVED_NAMES = (LOOKUP_OUT, WEIGHTS_NET_CONV)

_save_these = jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES)
_saved: dict[str, set] = {}


def save_named(prim, *avals, **params) -> bool:
    """``jax.checkpoint`` policy: a value named by one of
    :data:`SAVED_NAMES` is saved, everything else is recomputed."""
    if not _save_these(prim, *avals, **params):
        return False
    # jax asks once per partial evaluation of the body, and a scan
    # evaluates its body more than once: a value is counted by what it is.
    _saved.setdefault(params["name"], set()).add(tuple(map(str, avals)))
    return True


def reset_saved_residuals() -> None:
    _saved.clear()


def saved_residuals() -> dict:
    """``{name: distinct values saved under it}`` over every name of
    :data:`SAVED_NAMES`, since the last reset; 0 where the traced program
    has no such site, or builds no checkpoint."""
    return {name: len(_saved.get(name, ())) for name in SAVED_NAMES}
