"""A 64-byte file mapped by the benchmark's processes: the few numbers
they tell each other while a run is on.  The main process (which holds the
chip and the product) never waits on a generator and a generator never
asks the product anything: the drain observer writes `processed` here.

    0  int64    processed   lines drained so far            (main writes)
    8  int64    stop        1 = generators end their feed   (main writes)
   16  float64  t_go        wall time the feeds start at    (main writes)
   24  int64    gen_ready   line generator has its pools    (generator)
   32  int64    written     lines written so far            (generator)
"""

from __future__ import annotations

import mmap
import struct

SIZE = 64
PROCESSED, STOP, T_GO, GEN_READY, WRITTEN = 0, 8, 16, 24, 32


class Ctl:
    def __init__(self, path: str, create: bool = False):
        if create:
            with open(path, "wb") as f:
                f.write(b"\0" * SIZE)
        self._f = open(path, "r+b")
        self._m = mmap.mmap(self._f.fileno(), SIZE)

    def get(self, off: int) -> int:
        return struct.unpack_from("<q", self._m, off)[0]

    def put(self, off: int, v: int) -> None:
        struct.pack_into("<q", self._m, off, int(v))

    def get_f(self, off: int) -> float:
        return struct.unpack_from("<d", self._m, off)[0]

    def put_f(self, off: int, v: float) -> None:
        struct.pack_into("<d", self._m, off, float(v))

    def close(self) -> None:
        self._m.close()
        self._f.close()
