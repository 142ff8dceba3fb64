"""Build a TpuMatcher on the classic bitmap protocol the way the product
gets there: the window-scan selftest fails at construction
(`TpuMatcher._resolve_single_kernel`), the matcher notes the downgrade and
keeps `_fw_pipeline = None`.  The differential suites compare the fused
single-kernel path against this — the same exact path an overflowing
chunk or a host-evaluated rule takes — and against `CpuMatcher`."""

import contextlib

import pytest

from banjax_tpu.matcher.kernels import fused_match_window


def _selftest_fails(*a, **k):
    raise RuntimeError("synthetic window-scan lowering failure")


@contextlib.contextmanager
def scan_selftest_failing(failing: bool = True):
    """Every TpuMatcher constructed inside stays on the classic protocol
    (`failing=False`: nothing is patched, for a call site that builds
    either side)."""
    with pytest.MonkeyPatch.context() as mp:
        if failing:
            mp.setattr(fused_match_window, "scan_selftest", _selftest_fails)
        yield
