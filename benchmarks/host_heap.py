"""The line ``run_cell.steady_allocator`` lacks, for a cell whose tree has
no small leaf.  INTERIM: a ``model_config`` PR may not edit ``run_cell.py``;
the ``benchmark`` PR that may moves :func:`hold` into ``steady_allocator``
for every cell and this file goes (PERF.md section 7 (xiv)).

``steady_allocator`` pins glibc's mmap threshold at 32 MiB, the most
``mallopt`` allows: a host buffer over it is a fresh ``mmap`` whose pages
are faulted in one by one and unmapped on ``free``, UNLESS the heap has a
free chunk that holds it.  On the chip's sandboxed host a pass over
freshly mapped pages runs at 1 GB/s.  What a run does on the host after
its rounds (``compare.py``'s float64 passes over every leaf, the tap's
copies, the optimizer's state between the reference's steps) walks some
fifteen trees.  A tree with leaves under the threshold grows the heap, and
its large buffers are then carved from what those leaves freed
(``moonlight_16b_c3``: ``diff_norms`` 11.9 s, ``fedavg_gap`` 2.2 s); a tree
whose every matrix is over it pays a fault for every page of every pass
(``nemotron_twotower_30b_c5``: 40.3 and 24.9 s, the reference's share of a
warm run 131.5 s against 49.2 with :func:`hold`; my chip runs, PR 36), and
the run's 360 s do not hold that.

:func:`hold` serves large buffers from the heap (``mallopt(M_MMAP_MAX,
0)``), where they stay for reuse.  The price is memory: freed buffers stay
resident, what compiling left among them (a cold run peaked at 39.6 GiB on
a machine whose limit is given as 40), so one thread hands the heap's free
pages back (``malloc_trim``) while the process stands over
:func:`ceiling_bytes`.
"""

from __future__ import annotations

import ctypes
import os
import threading
import time

M_MMAP_MAX = -4
# the share of the machine's memory (MemTotal, or the cgroup's limit where
# it is less) the process may stand on before the heap's free pages go
# back: 36 of the chip machine's 45 GiB
SHARE = 0.8
_WATCH: threading.Thread | None = None


def ceiling_bytes() -> int:
    total = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    for path in ("/sys/fs/cgroup/memory.max",
                 "/sys/fs/cgroup/memory/memory.limit_in_bytes"):
        try:
            with open(path) as f:
                total = min(total, int(f.read()))
        except (OSError, ValueError):      # no such file, or "max"
            pass
    return int(SHARE * total)


def resident_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def hold() -> bool:
    """Large host buffers from the heap from now on, one watching thread a
    process; False where there is no glibc."""
    global _WATCH
    try:
        libc = ctypes.CDLL("libc.so.6")
        if libc.mallopt(M_MMAP_MAX, 0) != 1:
            return False
    except (OSError, AttributeError):
        return False
    ceiling = ceiling_bytes()

    def watch():
        while True:
            time.sleep(1.0)
            if resident_bytes() > ceiling:
                libc.malloc_trim(0)
    if _WATCH is None:
        _WATCH = threading.Thread(target=watch, daemon=True,
                                  name="bench-host-heap")
        _WATCH.start()
    return True
