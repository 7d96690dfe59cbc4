"""Closed-loop bulk ingest of backlogged traffic: the ingest loop
(``bench/loops/ingest.py``) as it is, plus the window's real and dispatched
cells of the cone scan and of the rANS encoder, from the program's own
counters (``repro.kernels.calls.cell_counts``), as ``<kernel>_real_cells``
and ``<kernel>_run_cells``.  A program that does not count a kernel's cells
leaves its counters out, and the metrics that read them find nothing."""
from __future__ import annotations

from bench.loops import ingest


class Cell(ingest.Cell):
    def window(self, spans) -> float:
        from repro.kernels.calls import cell_counts

        before = cell_counts()
        window_s = super().window(spans)
        for name, (real, run) in cell_counts().items():
            real0, run0 = before.get(name, (0, 0))
            self.counters[f"{name}_real_cells"] = real - real0
            self.counters[f"{name}_run_cells"] = run - run0
        return window_s
