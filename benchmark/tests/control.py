"""The controls of ``correct``: the plain reference put in the program's
place with one guarantee of the configuration broken, counted by the same
comparison as a run's answers. Each must come out as not correct.

    python benchmark/tests/control.py --workload <cell> --seeds 1,2,3 --seconds 8

For each seed one short window of the cell runs on the chip at the cell's
own size (the program's reading: ``wrong_answers`` 0), then every timed
request's rows are replaced by the control's and counted again:

  float32_sums             every product and SUM carried in float32 instead
                           of exact integers - the step a faster MXU path
                           would be tempted to take ("exact answers" broken)
  bfloat16_values          the metric columns carried as bfloat16, the
                           MXU's native input, instead of exact integer
                           limbs ("exact answers" broken)
  first_segment_missing    the table's first segment left out of every
                           answer ("complete" broken)

``--rehearse`` does the same on the CPU at toy size (tests/test_control.py).
One JSON line per seed; exit code 0 only if the program was correct on every
seed and every control failed on every seed.
"""

import argparse
import contextlib
import functools
import io
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import run as harness  # noqa: E402


def bfloat16(v):
    """Round to the nearest bfloat16 (ties to even), in ``v``'s own type."""
    bits = v.astype(np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(v.dtype)


def bfloat16_block(metrics, seg, block):
    return {c: bfloat16(v) if c in metrics else v for c, v in block.items()}


def without_first_segment(seg, block):
    return None if seg == 0 else block


def controls(config: dict) -> dict:
    """name -> (arithmetic of the sums, what is done to a segment's rows)."""
    metrics = [c for c, v in config["columns"].items()
               if v["role"] == "metric"]
    return {
        "float32_sums": ("float32", None),
        "bfloat16_values": ("exact",
                            functools.partial(bfloat16_block, metrics)),
        "first_segment_missing": ("exact", without_first_segment),
    }


def count_controls(run) -> dict:
    """Called by the harness once the program's own answers are compared:
    the same records with each control's answers in their place."""
    wrong = {}
    for name, (acc, block_of) in controls(run.config).items():
        answers = harness.reference_answers(
            run.cell, run.seed, run.rows_per_segment, run.records, acc,
            block_of)
        for r in run.records:
            r.rows, r.partial = answers[harness._key(r.cls, r.params)], False
        wrong[name] = harness.compare(run.records, run.workload,
                                      run.want)["wrong"]
    return {"control_wrong_answers": wrong}


def watch_memory(every_s: float = 0.5) -> None:
    """Resident memory of this process and its children on standard error,
    from a daemon thread (the chip's host ends a command at 40 GiB)."""
    import threading
    import time

    import psutil

    def loop():
        me, t0 = psutil.Process(), time.perf_counter()
        while True:
            rss = me.memory_info().rss
            for child in me.children(recursive=True):
                with contextlib.suppress(psutil.Error):
                    rss += child.memory_info().rss
            group = ""
            with contextlib.suppress(OSError, ValueError):
                info = dict(line.split(":") for line in open(
                    "/proc/meminfo").read().splitlines())
                group = "".join(
                    f" {k}={int(info[k].split()[0]) / 2 ** 20:.2f}"
                    for k in ("MemAvailable", "MemFree", "AnonPages",
                              "Cached", "Shmem", "Mapped", "Slab",
                              "PageTables", "Committed_AS") if k in info)
            with contextlib.suppress(OSError):
                group += " cgroup=" + open(
                    "/sys/fs/cgroup/memory/memory.usage_in_bytes"
                ).read().strip()
            print(f"[memory] {time.perf_counter() - t0:.0f}s "
                  f"{rss / 2 ** 30:.2f} GiB{group}", file=sys.stderr,
                  flush=True)
            time.sleep(every_s)

    threading.Thread(target=loop, daemon=True).start()


def read_seed(workload: str, seed: int, seconds: float, rehearse: bool):
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0"] + (["--rehearse"] * rehearse)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        code = harness.main(argv, after=count_controls)
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1]) if lines else None
    return code, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--watch-memory", action="store_true")
    args = ap.parse_args(argv)
    if args.watch_memory:
        watch_memory()
    ok = True
    for seed in map(int, args.seeds.split(",")):
        code, result = read_seed(args.workload, seed, args.seconds,
                                 args.rehearse)
        if result is None:
            print(json.dumps({"seed": seed, "exit": code, "result": None}),
                  flush=True)
            ok = False
            continue
        wrong = result["control_wrong_answers"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "attempted": result["attempted"],
            "program_correct": result["correct"],
            "program_compared": result["compared"],
            "control_wrong_answers": wrong,
            "device": result["device"]}), flush=True)
        ok = ok and result["correct"] and all(v > 0 for v in wrong.values())
    return 0 if ok else 1


if __name__ == "__main__":
    import os

    code = main()
    sys.stdout.flush()
    os._exit(code)
