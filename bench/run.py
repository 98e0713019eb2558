"""Run one cell of ``BENCHMARK.json`` once, on the chip this process finds.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, programming, compiling or loading every
program the cell's traffic uses) runs first and is reported as
``setup_s``; then the window measures for ``--seconds``; then the
program's state is freed and a plain reference re-reads a sample of what
the window produced. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit,
which also end standard error.

Without a TPU the run exits 1 and prints no result. ``--rehearse`` runs
the cell at the tiny sizes its files give for a CPU rehearsal; it then
reports counts and checks only and exits 3. ``--control 1`` also reads
the bfloat16 control of the reference (not used by the cell's own runs).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        import os
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from harness import runner
    code, result = runner.run(
        args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, allow_cpu=args.rehearse, rehearse=args.rehearse,
        control=bool(args.control))
    if result is not None:
        runner.emit(result)
    return code


if __name__ == "__main__":
    sys.exit(main())
