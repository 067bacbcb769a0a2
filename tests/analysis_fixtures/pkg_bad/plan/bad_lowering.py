"""Fixture plan/ module lowering straight onto kernels (the original
plan-import violation, both import forms)."""
from ..ops import bad_kernel  # SEEDED: layering/plan-no-ops
import pkg_bad.ops.bad_kernel as bk  # SEEDED: layering/plan-no-ops


def lower():
    return bad_kernel.bad_fn, bk.bad_fn
