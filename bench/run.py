"""Run one benchmark cell and print its result as the last stdout line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics with the device's busy time and a breakdown.  Both
print ``correct``, decided by the comparison with the plain reference,
and each compared number beside its limit (the last stderr lines, and
the ``checks`` key of the result).  Without the TPU chips the cell asks
for, the command exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import ctypes  # noqa: E402


def _keep_freed_memory():
    """Let the C allocator reuse what the process frees, as a long-lived
    training process would.  glibc maps every array over 32 MB afresh
    and unmaps it on free, so each job pays a page fault for every page
    of every large host array it makes, a cost that is large and uneven
    where mapping pages is slow (under a user-space kernel, say).  With
    no mapped chunks and no trimming, the window's jobs reuse the heap
    that the warm-up job grew.  Nothing the program computes changes."""
    try:
        libc = ctypes.CDLL("libc.so.6")
    except OSError:
        return
    libc.mallopt(-4, 0)  # M_MMAP_MAX
    libc.mallopt(-1, 2 ** 31 - 1)  # M_TRIM_THRESHOLD


_keep_freed_memory()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench import harness
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except harness.ChipMissing as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
