"""Which product ran at which dtype: a trace-time tally of a program's
product sites, in the manner of ``nn/layers.py::conv_forms`` (docs/
PRECISION.md "What ``bf16_train`` states"; docs/OBSERVABILITY.md).

A product site is a place in the model where many products are summed:
every ``Conv2d`` / ``SplitConv2d`` part (``nn/layers.py::conv2d``), every
normalized convolution of NCUP (``nn/nconv_unet.py``), the all-pairs
volume and each level's window contraction of the lookup (``ops/corr.py``).
Each records, while its program is traced, the dtype of its operands and the
dtype its sum is handed out in (``result``: the operands' own, rounded once
on the way out of the product, or the float32 accumulator where the site
asks for it), under ``<scope>/<site>``: the innermost :func:`scope` open
around it (``raft.fnet``, ``raft.update_block``, ``raft.upsample``, ...) and
the site's own name (a module path below the applied module, as
``conv_forms`` has it). On the TPU, and in XLA's CPU backend, a product of
bfloat16 operands is accumulated in float32 whichever way it is handed out;
no site of the model adds partial sums in a narrower dtype.

The tally costs a dictionary write per site per trace and nothing when a
compiled program runs. Sites are keyed by path, so a second trace of the
same program (``jax.checkpoint``, the backward's linearisation) changes
nothing. Reset before a program is lowered and read after it, in one thread
(``inference/costs.build_and_record`` does, for every executable it builds;
the discipline of ``ops/nconv.dispatch_counts``).
"""

from __future__ import annotations

import contextlib
import threading

import jax

_local = threading.local()
_sites: dict[str, dict] = {}


def _stack() -> list:
    if not hasattr(_local, "scopes"):
        _local.scopes = []
    return _local.scopes


@contextlib.contextmanager
def scope(name: str):
    """``jax.named_scope(name)`` that the tally sees too. The name stack of
    jax itself is no use here: a ``scan`` body and a ``jax.checkpoint`` are
    traced with an empty one and joined to their callers at lowering."""
    stack = _stack()
    stack.append(name)
    try:
        with jax.named_scope(name):
            yield
    finally:
        stack.pop()


def innermost_scope() -> str | None:
    """The name of the innermost :func:`scope` open in this thread."""
    stack = _stack()
    return stack[-1] if stack else None


@contextlib.contextmanager
def suspended():
    """Around code that jax traces LATE, after the scopes around its call
    site have closed (the forward and backward rules of a ``custom_vjp``):
    its products are the call site's own, recorded when that was traced."""
    before = getattr(_local, "suspended", False)
    _local.suspended = True
    try:
        yield
    finally:
        _local.suspended = before


def record_site(site: str, operands, result=None) -> None:
    """One product site of the program being traced: the dtype of its
    operands and of the sum it hands out (``None``: the operands')."""
    if getattr(_local, "suspended", False):
        return
    stack = _stack()
    path = f"{stack[-1]}/{site}" if stack else site
    operands = str(jax.numpy.dtype(operands))
    _sites[path] = {
        "operands": operands,
        "result": operands if result is None else str(jax.numpy.dtype(result)),
    }


def reset_product_sites() -> None:
    _sites.clear()


def product_sites() -> dict:
    """``{"<scope>/<site>": {"operands": dtype, "result": dtype}}`` of every
    product site traced since the last reset, sorted by path."""
    return {path: dict(_sites[path]) for path in sorted(_sites)}


def summarize_sites(sites: dict, policy_name: str) -> dict:
    """The line a start-up report carries beside an executable's phases:
    the policy it was traced under and how many sites took operands of
    which width."""
    narrow = sum(1 for s in sites.values() if s["operands"] == "bfloat16")
    return {
        "policy": policy_name,
        "sites_bf16": narrow,
        "sites_f32": len(sites) - narrow,
    }
