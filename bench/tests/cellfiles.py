"""Cells for the tests and tools: those of ``BENCHMARK.json``, and the LM
cells whose driver and reference are kept, out of ``BENCHMARK.json``,
until a comparison of their served tokens can be proved on the chip
(PERF.md, Open questions)."""
import json
import pathlib

from harness import cells

LM_CELLS = pathlib.Path(__file__).resolve().parent / "data" / "lm_cells.json"


def find(workload: str) -> cells.Cell:
    lm = json.loads(LM_CELLS.read_text())
    if workload in {w["name"] for w in lm["workloads"]}:
        return cells.build(lm, workload)
    return cells.find(workload)
