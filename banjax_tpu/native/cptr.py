"""A numpy array's C pointer for a ctypes call, without a reference
cycle.

`a.ctypes.data_as(t)` casts the array's `c_void_p`, and `ctypes.cast`
files a ctypes source object under its own `_objects`: a `c_void_p` and
a dict that point at each other, two objects a call that only the cyclic
collector can free — some seventy pairs a pipeline batch, garbage that
brings its collections on.  Cast from the address as an int there is no
source object to file."""

from __future__ import annotations

import ctypes

import numpy as np


def array_ptr(a: np.ndarray, t):
    """→ a `t` (a ctypes pointer type) at `a`'s first byte; it keeps `a`
    alive as long as it lives itself, as `data_as` does."""
    p = ctypes.cast(a.ctypes.data, t)
    p._arr = a
    return p
